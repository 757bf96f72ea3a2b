from layerlib import device_lane_share_pct as read  # noqa: F401
