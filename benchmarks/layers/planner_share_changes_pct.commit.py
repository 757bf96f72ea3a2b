"""Of the window's split calls, the share whose device share differs from
the split call before it (the hybrid's `share_changes` / `split_calls`)."""
from spanlib import counter_ratio_pct


def read(obs, run):
    return counter_ratio_pct(obs, "hybrid", "share_changes", "split_calls")
