"""``moe_router_ms`` (model code): device time a step under the expert
layers' scope ``moe_router``, forward and backward: in ``zaya1_8b`` the MLP
router with the state it is handed and hands on, its softmax and its one
choice. ``moe_ms`` and the section ``mlp`` of ``blocks_ms`` hold it too."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bmoe_router\b")
