from pathlib import Path

from bench import program_spans


def read(ctx):
    """Host seconds per completed build in ``repro.score.seed`` (k-means++
    seeding, with the compiles nested in it)."""
    s = program_spans.for_ctx(ctx, Path(__file__).resolve().parents[2])
    if s is None or "repro.score.seed" not in s.total_s or not ctx.completed:
        return None
    return s.total_s["repro.score.seed"] / ctx.completed
