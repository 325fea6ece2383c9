"""Arithmetic from samples to the numbers the benchmark prints.

Kept here, under the benchmark's paths, so that no later PR can change how
a metric is computed from what was measured.
"""
import hashlib
import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) of `values`, linear between ranks
    (numpy's default); None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median — the contract's measure of how widely runs disagree."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def token_gaps(stamps):
    """Gaps between consecutive output tokens of one request, from the
    times its tokens were seen. One token has no gap."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def ttft_ms_per_prompt_token(ttfts_s, prompt_tokens):
    """Sum of times to first token over sum of prompt tokens, in ms: every
    sample counts, and long prompts weigh as much as they cost."""
    total = sum(prompt_tokens)
    return 1e3 * sum(ttfts_s) / total if total else None


def composition_hash(rows):
    """Short hash of a sequence of per-step tuples, to compare the work of
    two runs without printing it."""
    text = ";".join(",".join(str(int(v)) for v in row) for row in rows)
    return hashlib.sha256(text.encode()).hexdigest()[:16]
