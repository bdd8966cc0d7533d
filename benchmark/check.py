"""The comparison that decides ``correct``: the program's first steps against
the plain reference's, number by number, each with a limit of its own.

The reference gives {"loss": {step: value}, "grad": {leaf: norm}, "moment":
{leaf: norm}, "change": {leaf: norm}} (see ``reference/train.py``).  The
program gives what its entry could read of these: "loss" for the steps
whose outputs it saw, and "grad" only where it can stop after one step (a
scan chunk hands back the state after its last step alone).  The numbers, n being the number of steps followed:

  loss<i>   |program - reference| / |reference| of step i's mean loss
  grad1_*   the gap between the two norms of a leaf's first gradient as
            the optimizer gets it, over the reference's norm of that leaf
            or of the median leaf, whichever is larger: the worst leaf, the
            leaf at nine tenths, and the median leaf
  mom<n>_*  the same of the optimizer's first slot after the n steps (Adam's
            first moment, SGD's momentum): every step's gradient as the
            optimizer got it, folded by the optimizer's own rule
  change<n>_*  the same of the parameters' change after the n steps, over
            the leaves whose reference gradient is at least a thousandth of
            the median leaf's (the others move by round-off alone)
  *_wmed, *_wworst  the median and the worst leaf among the operands of the
            products alone (the leaves of two axes or more: filters and
            matrices), which are what a lower precision of the products
            reaches first
"""
import math
import statistics


def leaf_gaps(program, reference, skip=()):
    """{leaf: |program norm - reference norm| / max(reference norm of the
    leaf, of the median leaf)}; a leaf the program lacks, or that is not
    finite there, reads infinity."""
    leaves = [k for k in reference if k not in skip]
    floor = max(statistics.median(reference[k] for k in leaves), 1e-30)
    gaps = {}
    for k in leaves:
        if k not in program or not math.isfinite(program[k]):
            gaps[k] = float("inf")
        else:
            gaps[k] = abs(program[k] - reference[k]) / max(reference[k],
                                                           floor)
    return gaps


def summarise(gaps):
    """The worst leaf, the leaf at nine tenths and the median leaf of the
    gaps: {"worst": (value, leaf), "p90": ..., "med": ...}."""
    order = sorted(gaps, key=gaps.get)
    pick = {"med": order[len(order) // 2],
            "p90": order[min(len(order) - 1, (9 * len(order)) // 10)],
            "worst": order[-1]}
    return {k: (gaps[leaf], leaf) for k, leaf in pick.items()}


def numbers(program, reference, matrices=()):
    """name -> (value, leaf or None) of every number read.  The limits file
    of a cell names those that are compared.  ``matrices`` names the leaves
    of two axes or more (a part ``name#i`` of a stacked leaf counts with its
    leaf)."""
    out = {}

    def with_wmed(prefix, gaps):
        for kind, gap in summarise(gaps).items():
            out[prefix + kind] = gap
        products = {k: v for k, v in gaps.items()
                    if k.split("#")[0] in matrices}
        if products:
            among = summarise(products)
            out[prefix + "wmed"] = among["med"]
            out[prefix + "wworst"] = among["worst"]

    n = len(reference["loss"])
    for i, a in sorted(program["loss"].items()):
        b = reference["loss"][i]
        gap = abs(a - b) / abs(b) if math.isfinite(a) else float("inf")
        out["loss%d" % i] = (gap, None)
    if program.get("grad"):
        with_wmed("grad1_", leaf_gaps(program["grad"], reference["grad"]))
    with_wmed("mom%d_" % n,
              leaf_gaps(program["moment"], reference["moment"]))
    med = statistics.median(reference["grad"].values())
    still = [k for k, g in reference["grad"].items() if g < 1e-3 * med]
    with_wmed("change%d_" % n,
              leaf_gaps(program["change"], reference["change"], skip=still))
    return out


def decide(nums, limits):
    """(correct, {name: [value, limit]}): every number the cell's limits
    file names is compared; a number without a limit there is an error, not
    a pass."""
    table, ok = {}, True
    for name, limit in limits.items():
        if name not in nums:
            raise KeyError("limits name %r, which this run did not read"
                           % name)
        value = nums[name][0]
        table[name] = [value, limit]
        ok = ok and value <= limit
    return ok, table
