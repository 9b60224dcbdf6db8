"""Backend build seconds (compile or persistent-cache load) of the programs
``setup.other_programs`` counts; the ten largest by function name go to
standard error."""
from benchmarks.harness import set_up


def read(ctx):
    builds = set_up.builds_before_window(ctx)
    if builds is None:
        return None
    mine = set_up.others(builds)
    set_up.say_largest(mine)
    return sum(b["s"] for b in mine)
