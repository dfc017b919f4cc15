"""``compile_s`` (entry points): seconds jax spent in backend compiles (or in
loading them from the persistent cache) during set-up, summed from jax's own
monitoring events. The cache's requests and hits go on an earlier line."""


def read(ctx):
    c = ctx["run"]["compile_in_setup"]
    ctx["say"](f"set-up compiles: {c['compiles']} in {c['compile_s']:.2f} s; "
               f"persistent cache {c['cache_hits']} hits of "
               f"{c['cache_requests']} requests")
    return c["compile_s"]
