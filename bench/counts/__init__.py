"""Operations and state bytes of one layer, one module per kind of the
program's ``LayerSpec`` (``counts/<kind>.py``), summed over a model's
layers by ``bench/flops.py``.  Each module gives:

- ``flops(m, spec, ctx)``: the useful FLOPs of one token through one
  layer of that kind, the token attending over ``ctx`` positions;
- ``state_bytes(m, spec, batch, ctx)``: the bytes of the layer's cache
  or state that a decode step of ``batch`` sequences, each holding
  ``ctx`` positions, reads.

``m`` is a configuration's ``model`` sizes, ``spec`` the layer's
``LayerSpec``.
"""

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def itemsize(m: dict) -> int:
    """Bytes of one element in the dtype the model serves in."""
    return ITEMSIZE[m.get("dtype", "bfloat16")]
