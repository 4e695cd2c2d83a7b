"""Operations and bytes a training step needs, from the configuration's shapes.

The forward arithmetic is copied from the program's analytic model
(``benchmarks/flops_model.py``, ``fwd_flops``) and kept here so that no PR
can change the yardstick: causal attention counts the S(S+1)/2 attended
pairs, the head runs over the padded vocabulary.  A training step needs its
forward and a backward of twice the forward; recomputation (remat, the
kernels' own recompute) is not counted.
"""
from __future__ import annotations

from perfbench import weights

BWD_OVER_FWD = 2.0


def _dims(model: dict):
    dh = model["head_dim"]
    return (model["hidden_size"], model["num_attention_heads"] * dh,
            model["num_key_value_heads"] * dh)


def fwd_flops(model: dict, batch: int, seq: int) -> dict:
    """Forward FLOPs of one step by component: proj, attention, mlp, head."""
    d, qd, kvd = _dims(model)
    n_layers = model["num_hidden_layers"]
    n_mats = 3 if model["hidden_act"] == "silu" else 2
    t = batch * seq
    return {
        "proj": n_layers * (2 * t * d * (qd + 2 * kvd) + 2 * t * qd * d),
        "attention": n_layers * 4 * batch * (seq * (seq + 1) / 2) * qd,
        "mlp": n_layers * n_mats * 2 * t * d * model["intermediate_size"],
        "head": 2 * t * d * weights.padded_vocab(model),
    }


def step_flops(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward plus backward, no recompute."""
    return (1 + BWD_OVER_FWD) * sum(fwd_flops(model, batch, seq).values())


def attention_work(model: dict, batch: int, seq: int, act_bytes: int = 2) -> dict:
    """The least work of one step's causal attention, forward and backward.

    FLOPs: QK^T and PV forward; dV, dP, dQ, dK backward.  Bytes: q, k, v read
    and o written forward; q, k, v, o, dO read and dq, dk, dv written
    backward, at ``act_bytes`` per element."""
    _, qd, kvd = _dims(model)
    fl = (1 + BWD_OVER_FWD) * fwd_flops(model, batch, seq)["attention"]
    t = batch * seq * model["num_hidden_layers"]
    fwd_bytes = t * (2 * qd + 2 * kvd) * act_bytes
    bwd_bytes = t * (4 * qd + 4 * kvd) * act_bytes
    return {"flops": fl, "bytes": fwd_bytes + bwd_bytes}
