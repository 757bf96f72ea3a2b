from layerlib import lanes_per_dispatch as read  # noqa: F401
