from pathlib import Path

from bench import program_spans


def read(ctx):
    """Share of the traced window in which the device ran no op while the
    host's innermost program span was ``repro.stage``."""
    s = program_spans.for_ctx(ctx, Path(__file__).resolve().parents[2])
    if s is None or "repro.stage" not in s.count or s.window_s <= 0:
        return None
    return 100.0 * s.idle_s.get("repro.stage", 0.0) / s.window_s
