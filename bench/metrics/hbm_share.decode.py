"""Bytes a decode step must read (weights held on the fullest chip, but
an embedding table that is not also the output head, since only its
rows are gathered; plus the valid KV cache) over the device's busy time
per traced step, over the HBM peak (%)."""

from bench import spec


def read(facts):
    tr = facts.get("trace")
    if not tr or not facts.get("traced_steps") or not tr["busy_s"]:
        return None
    bw = spec.peaks(facts["device_kind"])["hbm_bytes_per_s"]
    step_s = tr["busy_s"] / facts["traced_steps"]
    return 100.0 * facts["step_read_bytes"] / step_s / bw
