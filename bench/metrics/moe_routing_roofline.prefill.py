"""Share of its roofline the moe_routing kernel reaches in prefill: the
least time its bytes and operations need at the chip's peaks
(bench/flops.py), over the kernel's device time (trace, %)."""

from bench import flops, spec

KERNEL = "moe_routing"


def read(facts):
    tr = facts.get("trace")
    if not tr or "routing_shape" not in facts:
        return None
    t = sum(v for k, v in tr["op_s"].items() if KERNEL in k)
    n = sum(v for k, v in tr["op_count"].items() if KERNEL in k)
    if not t or not n:
        return None
    peaks = spec.peaks(facts["device_kind"])
    ops, nbytes = flops.moe_routing(*facts["routing_shape"])
    least = max(ops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * n / t
