"""Expert parallelism (Switch-style mixture-of-experts) over an
``expert`` mesh axis.

Beyond reference parity — upstream dmlc-core has no model math
(SURVEY.md §2e marks EP absent) — but the substrate reserves the
``expert`` axis; this populates it the TPU way: experts shard over the
axis, and tokens move to their expert and back as TWO ``all_to_all``
collectives riding ICI (the reference world would build this with NCCL
all-to-all + a CUDA dispatch kernel).

Formulation (inside ``shard_map``; E experts over P shards, E/P each):

1. route: top-1 over router logits, gate = that expert's softmax prob
   (Switch Transformer); per-expert positions by cumsum, tokens beyond
   the capacity ``C = ceil(cf · T / E)`` are DROPPED (output 0 — the
   caller's residual connection carries them, standard Switch behavior);
2. dispatch: a ``[T, E, C]`` one-hot einsum packs tokens into per-expert
   slots — gather-free, MXU-friendly, static shapes;
3. ``all_to_all`` the ``[P, E_local, C, D]`` slabs so every shard holds
   ALL shards' slots for ITS experts; batched expert FFN; ``all_to_all``
   back; combine with gate · dispatch.

An auxiliary load-balancing loss (mean expert fraction · mean router
prob, Switch eq. 4) is returned so trainers can keep routing uniform.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

__all__ = ["moe_ffn", "reference_moe_ffn"]


def moe_ffn(
    x: jax.Array,            # [T, D] local tokens
    wr: jax.Array,           # [D, E] router (replicated)
    w1: jax.Array,           # [E_local, D, F] this shard's experts
    b1: jax.Array,           # [E_local, F]
    w2: jax.Array,           # [E_local, F, D]
    b2: jax.Array,           # [E_local, D]
    axis: Optional[str] = "expert",
    capacity_factor: float = 1.25,
    stats: bool = False,
) -> Tuple[jax.Array, Any]:
    """Top-1 expert FFN; returns ``(y [T, D], aux_loss scalar)``.

    ``axis=None`` runs the same math unsharded (w1 then holds ALL
    experts) — the single-device reference path and the oracle the
    sharded run is tested against.

    ``stats=True`` returns ``(y, (assign_sum [E], prob_sum [E], T))``
    instead of the scalar aux: raw routing-statistic SUMS the caller can
    psum over its batch axes and combine into the aux loss GLOBALLY —
    the only way a data-sharded trainer reproduces the unsharded aux
    exactly (a mean of per-shard aux values is a different statistic).
    """
    T, D = x.shape
    E = wr.shape[1]
    P = axis_size(axis) if axis is not None else 1
    e_local = w1.shape[0]
    cap = max(1, int(np.ceil(capacity_factor * T / E)))

    logits = x @ wr                                       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)               # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], 1)[:, 0]

    onehot = jax.nn.one_hot(expert_idx, E, dtype=x.dtype)  # [T, E]
    # aux load-balance loss (Switch eq. 4): E · Σ_e fraction_e · prob_e
    assign_sum = jnp.sum(onehot, axis=0)                  # [E]
    prob_sum = jnp.sum(probs, axis=0)                     # [E]
    aux = E * jnp.sum((assign_sum / T) * (prob_sum / T))
    pos = (jnp.cumsum(onehot, axis=0) * onehot).astype(jnp.int32)  # 1-based
    keep = (pos > 0) & (pos <= cap)
    slot = jax.nn.one_hot(pos - 1, cap, dtype=x.dtype) * keep[..., None]
    dispatch = onehot[..., None] * slot                   # [T, E, C]

    xe = jnp.einsum("tec,td->ecd", dispatch, x)           # [E, C, D]
    if axis is not None:
        # send each expert-slab to its owner; receive every shard's
        # tokens for the local experts: [P, E_local, C, D]
        xe = xe.reshape(P, e_local, cap, D)
        xe = lax.all_to_all(xe, axis, split_axis=0, concat_axis=0,
                            tiled=False)
        xe = jnp.moveaxis(xe, 0, 1).reshape(e_local, P * cap, D)
    # batched expert FFN on [E_local, slots, D]
    h = jax.nn.gelu(jnp.einsum("esd,edf->esf", xe, w1) + b1[:, None, :])
    ye = jnp.einsum("esf,efd->esd", h, w2) + b2[:, None, :]
    if axis is not None:
        ye = jnp.moveaxis(ye.reshape(e_local, P, cap, D), 1, 0)
        ye = lax.all_to_all(ye, axis, split_axis=0, concat_axis=0,
                            tiled=False)
        ye = ye.reshape(E, cap, D)
    y = jnp.einsum("tec,ecd->td", dispatch, ye) * gate[:, None]
    if stats:
        return y, (assign_sum, prob_sum, jnp.float32(T))
    return y, aux


def reference_moe_ffn(x, wr, w1_all, b1_all, w2_all, b2_all,
                      capacity_factor=1e9):
    """Numpy oracle: per-token dense expert application (no capacity
    pressure unless ``capacity_factor`` is set low, matching moe_ffn's
    drop rule)."""
    x = np.asarray(x)
    T, D = x.shape
    E = np.asarray(wr).shape[1]
    cap = max(1, int(np.ceil(capacity_factor * T / E)))
    logits = x @ np.asarray(wr)
    z = np.exp(logits - logits.max(-1, keepdims=True))
    probs = z / z.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    gate = probs[np.arange(T), idx]
    y = np.zeros_like(x)
    counts = np.zeros(E, np.int64)
    for t in range(T):
        e = idx[t]
        counts[e] += 1
        if counts[e] > cap:
            continue                       # dropped: residual only
        h = x[t] @ np.asarray(w1_all)[e] + np.asarray(b1_all)[e]
        h = 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi)
                                   * (h + 0.044715 * h ** 3)))
        y[t] = (h @ np.asarray(w2_all)[e] + np.asarray(b2_all)[e]) * gate[t]
    return y
