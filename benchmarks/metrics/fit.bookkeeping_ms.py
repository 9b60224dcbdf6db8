"""Median over the traced steps of ``step_prep`` + ``step_install``: the
update counts, ``lrs`` / ``wds`` / ``ts`` and the gather of raw buffers
before the dispatch, the re-install of every donated buffer after it."""
from benchmarks.harness import program_spans as ps


def read(ctx):
    v = ps.view(ctx)
    return None if v is None else v.step_ms((ps.PREP, ps.INSTALL))
