"""Sizes at which ``test_bench_harness.py`` rehearses the cells of the
qwen2-moe-a2.7b configuration on CPU devices.

That file sizes each configuration from its ``SMOKE`` and
``CONTROL_SIZE`` tables, keyed by the configuration's name, and runs
every cell of BENCHMARK.json with them; the entries below join those
tables before its tests run.  The checks themselves are that file's.
"""

from bench.tests import test_bench_harness as harness_tests

QWEN = "qwen2-moe-a2.7b"
# every kind of the published layer at small widths: routed experts
# over the 4 ranks (8 padded to 16, 4 a rank), a shared expert of
# n_shared_experts x d_expert_ff, an untied head
harness_tests.SMOKE.setdefault(QWEN, dict(
    name="qwen2-smoke", family="moe", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=128, d_expert_ff=32, vocab=256, n_experts=8,
    top_k=2, n_shared_experts=4, head_dim=16, tie_embeddings=False,
    dtype="bfloat16"))
# the control's size: the published vocabulary, more layers and width
harness_tests.CONTROL_SIZE.setdefault(QWEN, dict(
    harness_tests.SMOKE[QWEN], n_layers=8, d_model=128, head_dim=32,
    vocab=151936))
