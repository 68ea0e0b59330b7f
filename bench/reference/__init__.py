"""Plain references that decide ``correct``; they import nothing of the
program under test.

A configuration names its reference module in its ``"reference"`` key:
``bench/reference/<name>.py``, found by ``spec.reference``.  Every such
module offers:

- ``Reference(model, shapes, seed, tp=1, precision="f32", device=None,
  draws=None)``: the reference model for one configuration and seed.
  ``model`` is the configuration's ``model`` sizes; ``shapes`` the
  served layout, {"top": {name: shape}, "blocks": [{name: per-layer
  shape}]} (``modelcell.served_shapes``); ``tp`` the ranks the program
  spreads its experts over; ``draws`` the configuration's ``weights``
  block (``bench/weights.py``).  Weights are redrawn from the seed with
  ``bench/weights.py``, bit-identical to the served ones.  ``precision``
  is ``"f32"`` (the reference: float32 products at
  ``Precision.HIGHEST``), ``"fp8"`` (the control: every matrix product
  takes its operands rounded to float8_e4m3fn under a per-tensor scale)
  or ``"bf16"`` (the witness: operands rounded to bfloat16, what
  rounding alone does at the precision the program serves in).  It has
  ``hidden(tokens, group_id, keep_pos)``: the final-normed hidden states
  (B, len(keep_pos), d) of the teacher-forced sequences ``tokens``
  (B, T), ``group_id`` (B, T) naming the step each token was served in;
  ``head()``: the output head (d, V) over the real vocabulary;
  ``logits(h, head)``; and ``device``.
- ``gap_values`` and ``summary``: the module's own or the ones below,
  which judge any decoder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _gaps(ref_logits, chosen):
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]
    return best - got


def gap_values(ref, h_ref, served: np.ndarray | None = None, ctrl=None,
               h_ctrl=None, chunk: int = 8) -> np.ndarray:
    """(B, P): by how much the chosen token's reference logit lies below
    the reference's best at each compared position.  The chosen token
    is the served one, or the control's first choice."""
    head = ref.head()
    P = h_ref.shape[1]
    out = []
    for p0 in range(0, P, chunk):
        sl = slice(p0, min(P, p0 + chunk))
        lr = ref.logits(h_ref[:, sl], head)
        if ctrl is None:
            chosen = jax.device_put(jnp.asarray(served[:, sl], jnp.int32),
                                    ref.device)
        else:
            chosen = jnp.argmax(ctrl.logits(h_ctrl[:, sl], head), -1)
        out.append(np.asarray(_gaps(lr, chosen)))
    return np.concatenate(out, 1)


def summary(g: np.ndarray) -> dict:
    """The widest gap (``max_logit_gap``), the mean gap and the share of
    positions whose chosen token is not the reference's first."""
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "mismatch_share": float(np.mean(g > 0))}
