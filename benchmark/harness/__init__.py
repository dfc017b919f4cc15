"""The yardstick: everything a run needs besides the system under test."""
