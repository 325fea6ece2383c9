"""A statistic, in ms, of the harness's own span around a call into a
layer (`result["spans"][span]`, seconds on the host clock, window only)."""
import statistics

from chipbench import stats


def read(result, span, stat):
    values = result["spans"].get(span)
    if not values:
        return None
    if stat == "mean":
        return 1e3 * statistics.fmean(values)
    return 1e3 * stats.percentile(values, float(stat.lstrip("p")))
