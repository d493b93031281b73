"""Delay from a request's last token to its client's next submit: how
late the closed loop's own clients ran."""

from chipbench import stats


def read(run, q):
    delays = getattr(run, 'resubmit_delays', None)
    if not delays:
        return None
    return 1e3 * stats.percentile(delays, q)
