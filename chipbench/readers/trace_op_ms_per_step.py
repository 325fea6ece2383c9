"""Device time, in ms per traced step and per chip, of the operations
whose trace name contains any of `match` (self time: a `while` around a
layer loop does not count its body again)."""
from chipbench import xplane


def read(result, match):
    if not result.get("trace") or not result.get("traced_steps"):
        return None
    seconds = xplane.seconds_matching(result["trace"], match)
    if seconds <= 0:
        return None
    return 1e3 * seconds / result["traced_steps"]
