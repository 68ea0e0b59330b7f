"""Prefill model FLOP utilisation: useful FLOPs per batch (bench/flops.py)
times batches in the window, over the window, over chips x bf16 peak (%)."""

from bench.metrics._mfu import mfu


def read(facts):
    return mfu(facts) if facts.get("kind") == "prefill" else None
