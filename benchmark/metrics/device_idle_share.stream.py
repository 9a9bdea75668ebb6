"""Device: 1 - (union of device-operation intervals / traced window)."""

from common import idle_share as read  # noqa: F401
