"""Device: share of the traced window in which no operation ran."""
from bench.readers import idle_share as read  # noqa: F401
