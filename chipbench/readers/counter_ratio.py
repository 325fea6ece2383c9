"""One count of the run over another, times `scale` (100 for a share in
percent). Counts are sums over the window's scheduler steps."""


def read(result, num, den, scale=100.0):
    counters = result["counters"]
    if num not in counters or not counters.get(den):
        return None
    return scale * counters[num] / counters[den]
