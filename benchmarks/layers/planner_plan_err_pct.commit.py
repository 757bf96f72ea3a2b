"""How far the planner's predicted wall was from the measured one, over the
window's calls that reached the device: the hybrid's `plan_abs_err_ms` /
`wall_ms` (sums of |predicted - measured| and of the measured wall, a
program's first use left out). `planner_share_changes_pct.commit` says
whether the share holds still; this says whether the model behind it is right."""
from spanlib import counter_ratio_pct


def read(obs, run):
    return counter_ratio_pct(obs, "hybrid", "plan_abs_err_ms", "wall_ms")
