"""Model FLOPs over the window over the chips' peak (shared by the
``mfu.*`` readers)."""

from bench import spec


def mfu(facts):
    if "model_flops" not in facts:
        return None
    peak = spec.peaks(facts["device_kind"])["bf16_flops"]
    return 100.0 * facts["model_flops"] / facts["window_s"] / (
        facts["chips"] * peak)
