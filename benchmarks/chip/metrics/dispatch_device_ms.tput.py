"""Device busy time in the window per allocation dispatch."""
from _common import dispatch_device_ms as read  # noqa: F401
