"""Host time per engine step: the step span minus device busy inside it."""
from _common import host_ms as read  # noqa: F401
