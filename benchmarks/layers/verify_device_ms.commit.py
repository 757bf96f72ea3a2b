from layerlib import verify_device_ms as read  # noqa: F401
