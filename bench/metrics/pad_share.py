"""Paged runner (core/paged_runner.py): share of the token slots of the
window's fused steps that padding filled, from the runner's counters:
every step pads its rows and tokens to a power-of-two ``(B, C)`` bucket
(``bucket_tokens``), and carries ``prefill_tokens`` + ``decode_tokens``
real ones.  None where the program does not count bucket slots."""


def read(run):
    a, b = run.stats_open["runner"], run.stats_close["runner"]
    if "bucket_tokens" not in a:
        return None
    slots = b["bucket_tokens"] - a["bucket_tokens"]
    if slots <= 0:
        return None
    real = (b["prefill_tokens"] - a["prefill_tokens"]
            + b["decode_tokens"] - a["decode_tokens"])
    return 100 * (1 - real / slots)
