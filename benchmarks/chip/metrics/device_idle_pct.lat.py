"""Share of the traced window in which device 0 ran no op."""
from _common import idle_pct as read  # noqa: F401
