"""Of the lanes the hybrid tier ran on the device over the window
(`device_lanes`), the share verified against a resident key column's window
tables (`resident_lanes`): the engagement of the table-sum program. 100
while one validator set signs every commit; it falls when the set starts to
change inside a cell. A program without the counter gives nothing to read."""
from layerlib import delta


def read(obs, run):
    if "resident_lanes" not in obs.counters_after["hybrid"]:
        return None
    device = delta(obs, "hybrid", "device_lanes")
    if device <= 0:
        return None
    return 100.0 * delta(obs, "hybrid", "resident_lanes") / device
