"""Output tokens streamed inside the window over the window's seconds
(host clock)."""
from bench.readers import output_tok_s as read  # noqa: F401
