"""A share out of the program's registry: 100 x the window's difference
of the counters whose full name (``name{labels}``) matches ``numerator``,
summed, over that of those matching ``denominator``.  With ``largest``
(a label's name) only the counters whose label holds the largest number
are read, on both sides: the last hop.

None where the denominator did not move: a program without the counters,
or a run with the registry off."""
import re


def moved(ctx, pattern, largest=None) -> float:
    """The window's difference of the registry entries matching
    ``pattern``, summed."""
    before, after = ctx["registry"]
    rx = re.compile(pattern)
    names = [n for n in after if rx.search(n)]
    if largest and names:
        label = re.compile(r"[{,]" + re.escape(largest) + r"=(\d+)[,}]")
        found = [(int(label.search(n).group(1)), n) for n in names
                 if label.search(n)]
        top = max((k for k, _ in found), default=None)
        names = [n for k, n in found if k == top]
    return sum(after[n] - before.get(n, 0.0) for n in names)


def read(ctx, params):
    den = moved(ctx, params["denominator"], params.get("largest"))
    if not den:
        return None
    return 100.0 * moved(ctx, params["numerator"],
                         params.get("largest")) / den
