"""Decode model FLOP utilisation: useful FLOPs per step (bench/flops.py)
times steps in the window, over the window, over chips x bf16 peak (%)."""

from bench.metrics._mfu import mfu


def read(facts):
    return mfu(facts) if facts.get("kind") == "decode" else None
