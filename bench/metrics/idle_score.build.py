from pathlib import Path

from bench import program_spans


def read(ctx):
    """Share of the traced window in which the device ran no op while the
    host's innermost program span was ``repro.score`` or one of its
    children (``repro.score.*``)."""
    s = program_spans.for_ctx(ctx, Path(__file__).resolve().parents[2])
    if s is None or "repro.score" not in s.count or s.window_s <= 0:
        return None
    idle = sum(v for k, v in s.idle_s.items()
               if k == "repro.score" or (k or "").startswith("repro.score."))
    return 100.0 * idle / s.window_s
