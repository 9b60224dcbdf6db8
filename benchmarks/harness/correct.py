"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings of the first steps (see
``reference/resnet.py:run_steps``): each step's loss, the norm of the first
gradient per leaf, the norm of the parameters' change over the steps per
leaf, and the norm of the moving statistics' change per leaf. Compared
are relative gaps. A leaf's gap is the distance between the two norms (not
the norm of a difference) over the reference's norm of that leaf or of the
median leaf, whichever is larger.

Judged are the *median* leaf's gap and the gap of all leaves taken as
one vector (``*.total``); the worst leaf's is printed beside them
(``*.worst``) and has no limit. The look that led there (PERF.md,
Findings, PR 24): in bf16, as the configurations state it, the gradients
of the early stages' batch-norm scales and shifts are sums of some 800,000
cancelling products and read 20-60 % off the float32 reference in their
worst leaf on every seed, in the program and in a plain bf16 copy of the
reference alike, so the worst leaf measures bf16 and cannot tell the
program from the control or from a planted fault; the median leaf reads
0.4-0.6 % on every seed.
"""
import math
import statistics
import sys

#: leaves whose reference gradient is under this share of the median
#: leaf's are left out of ``param_change``: they move by round-off alone
TINY_GRADIENT = 1e-3


def leaf_gaps(prog, ref, skip=()):
    """``{leaf: gap}``: the distance between the two sides' norms over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    names = [k for k in ref if k not in skip]
    floor = statistics.median(ref[k] for k in names)
    gaps = {}
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    return gaps


def _total(norms, names):
    return math.sqrt(sum(norms[k] ** 2 for k in names))


def _leaves(out, name, prog, ref, skip=()):
    gaps = leaf_gaps(prog, ref, skip)
    order = sorted(gaps, key=gaps.get)
    mid = order[(len(order) - 1) // 2]      # the lower median: a leaf
    out[name] = (gaps[mid], mid)
    # all leaves as one vector: rounding that is random from element to
    # element lengthens it by half its share of the power, whatever its
    # sign in a small leaf, so this reads a lower precision where the
    # median leaf does not
    a, b = _total(prog, gaps), _total(ref, gaps)
    gap = abs(a - b) / b
    out[name + ".total"] = (gap if math.isfinite(gap) else math.inf,
                            "%.6g vs %.6g" % (a, b))
    out[name + ".worst"] = (gaps[order[-1]], order[-1])


def compare(prog, ref):
    """``{number: (value, where)}`` from the two sides' readings."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        gap = abs(a - b) / abs(b)
        out["loss.%d" % (i + 1)] = (gap if math.isfinite(gap) else math.inf,
                                    "%.6g vs %.6g" % (a, b))
    _leaves(out, "grad_norm", prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    tiny = {k for k, v in ref["grad_norms"].items()
            if v < TINY_GRADIENT * med}
    _leaves(out, "param_change", prog["change_norms"], ref["change_norms"],
            skip=tiny)
    _leaves(out, "bn_stats", prog["aux_change_norms"],
            ref["aux_change_norms"])
    return out


def judge(numbers, limits):
    """``(correct, {name: {"value", "limit"}})``; a number with no limit in
    the cell's file is printed and not judged."""
    ok, table = True, {}
    for name, (value, where) in numbers.items():
        limit = limits.get(name)
        table[name] = {"value": value, "limit": limit, "at": where}
        if limit is not None and not value <= limit:
            ok = False
    return ok, table


def print_table(table, stream=None):
    stream = sys.stderr if stream is None else stream
    for name, row in table.items():
        print("compared %-18s %.6g  limit %s  (%s)"
              % (name, row["value"], row["limit"], row["at"]), file=stream)


def reference_readings(reference, kwargs, ref_inputs, **variant):
    """Run the plain reference over the compared steps (``variant``: the
    control's ``operand_round`` and ``state_dtype``)."""
    import jax.numpy as jnp
    params, aux = ref_inputs["params"], ref_inputs["aux"]
    batches = [(jnp.asarray(x), jnp.asarray(y))
               for x, y in ref_inputs["batches"]]
    return reference.run_steps(
        params, aux, batches,
        lr=ref_inputs["lr"], momentum=ref_inputs["momentum"],
        wd=ref_inputs["wd"], **variant, **kwargs)
