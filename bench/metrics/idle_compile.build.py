from pathlib import Path

from bench import program_spans


def read(ctx):
    """Share of the traced window in which the device ran no op while the
    host compiled or read the compilation cache (``repro.compile``)."""
    s = program_spans.for_ctx(ctx, Path(__file__).resolve().parents[2])
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * s.compile_idle_s / s.window_s
