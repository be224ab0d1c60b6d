from pathlib import Path

from bench import program_spans


def read(ctx):
    """Host seconds per completed build inside ``repro.stage``: superchunk
    assembly on the host and the ``device_put`` of the staging buffer."""
    s = program_spans.for_ctx(ctx, Path(__file__).resolve().parents[2])
    if s is None or "repro.stage" not in s.total_s or not ctx.completed:
        return None
    return s.total_s["repro.stage"] / ctx.completed
