"""Matcher host: XLA executables built inside the measured window
(reporter_xla_compiles_total); 0 after a complete warm-up."""

from common import window_compiles as read  # noqa: F401
