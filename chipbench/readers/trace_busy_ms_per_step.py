"""Device-busy time (union of every operation's interval), in ms per
traced step, averaged over the chips."""
from chipbench import xplane


def read(result):
    if not result.get("trace") or not result.get("traced_steps"):
        return None
    seconds = xplane.busy_seconds(result["trace"])
    if seconds <= 0:
        return None
    return 1e3 * seconds / result["traced_steps"]
