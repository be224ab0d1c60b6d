from pathlib import Path

from bench import program_spans


def read(ctx):
    """Host self seconds per completed build in ``repro.dis`` (Algorithm
    1's dispatch, less the staging and waits nested in it)."""
    s = program_spans.for_ctx(ctx, Path(__file__).resolve().parents[2])
    if s is None or "repro.dis" not in s.self_s or not ctx.completed:
        return None
    return s.self_s["repro.dis"] / ctx.completed
