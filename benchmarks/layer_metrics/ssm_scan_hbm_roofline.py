"""Share of the HBM bandwidth roofline the prefill's selective-scan
kernel reached. The bytes a call (one state-space layer of one prompt)
must move are its inputs and outputs once for every true token
(``ops_count_ssm.scan_bytes_per_token``), at the window's mean true
tokens a prefill (``serving.ssm.scan_tokens`` over the prefills). Over
the mean device time of an ``ssm_scan*`` call in the traced slice and
the chip's HBM bytes a second. Means on both sides; a call runs a padded
bucket, so useful bytes are under what it moves. The kernel is bound by
vector work (an exponential and six more operations an element of a
[16, E] state a position), not by bytes: the share is expected low, and
says how far from the memory bound the scan is."""

from benchmarks import ops_count_ssm


def read(ctx):
    trace, counters = ctx.get("trace"), ctx["counters"]
    prefills = (counters.get("serving.phase.prefill_forward_us")
                or {}).get("count", 0)
    tokens = counters.get("serving.ssm.scan_tokens", 0)
    if not trace or not prefills or not tokens or ctx["peaks"] is None:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if "ssm_scan" in name)
    calls = sum(n for name, n in trace["op_counts"].items()
                if "ssm_scan" in name)
    if not seconds or not calls:
        return None
    nbytes = tokens / prefills \
        * ops_count_ssm.scan_bytes_per_token(ctx["cell"].config)
    return 100.0 * nbytes / (seconds / calls) \
        / ctx["peaks"]["hbm_bytes_per_s"]
