"""SECDED-protected per-lane recurrent state (DESIGN.md §19).

A KV page is written once and read many times; a Mamba-2 layer's decode
state is read in full and rewritten every step. Between steps it lives only
here, in the ``ssm`` memory domain, as SECDED(72,64) word planes with two
float32 per codeword, as the KV pages hold them:

  * SSM state (H, P, N) per lane: codeword (head, i, n) packs row i of the
    head's state in ``ssm_lo`` and row i + P/2 in ``ssm_hi``, so the planes
    are (H, P/2, N) and the fused kernel (kernels/ecc_ssd.py) reads a head
    as one (P/2, N) tile;
  * conv tail (K-1, C) per lane: channel c in ``conv_lo``, c + C/2 in
    ``conv_hi``, planes (K-1, C/2).

One slot per lane, indexed by the lane, on a leading (groups, lanes) pair of
axes like the dense KV of the lane cache it sits in. Admission encodes the
prefill group's final state into the admitted lanes' slots (``commit``); the
next admission overwrites a slot. Every decode step decodes and corrects
each live slot, counting clean / corrected / detected words into the
layer's ``cnt`` (lanes, 3) row, and writes it back re-encoded; idle lanes
keep their planes and count nothing. ``arm`` opens a decode block with
zeroed counters and the lanes' live mask, ``harvest`` closes it and returns
the block's counts. No float copy of the state outlives a decode step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CODEC = "secded72"
PLANES = ("ssm_lo", "ssm_hi", "ssm_par", "conv_lo", "conv_hi", "conv_par")


def positions(cfg) -> tuple[int, ...]:
    """Period positions whose mixer keeps a recurrent state here."""
    return tuple(
        j for j in range(cfg.period) if cfg.layer_kind(j)["mixer"] == "mamba2"
    )


def words_per_lane(cfg) -> int:
    """Codewords one lane's slot holds over every state layer."""
    h = cfg.d_inner // cfg.ssm_head_dim
    conv = cfg.d_inner + 2 * cfg.d_state
    per_layer = h * cfg.ssm_head_dim * cfg.d_state // 2 + (cfg.d_conv - 1) * conv // 2
    return cfg.n_groups * len(positions(cfg)) * per_layer


def _pack(x, axis):
    """float32 -> (lo, hi, par): the two halves of ``axis`` as one codeword,
    check bits from the Pallas encoder (kernels/secded.py)."""
    from repro.kernels import ops as kops

    lo, hi = jnp.split(jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32), 2, axis)
    return lo, hi, kops.encode(lo, hi, codec=CODEC)


def _unpack(lo, hi, par, live, axis):
    """Decode and correct (Pallas decoder) -> (float32, (lanes, 3)
    clean/corrected/detected counts over live lanes). Lanes lead the
    planes' axes."""
    from repro.kernels import ops as kops

    lo, hi, status = kops.decode(lo, hi, par, codec=CODEC)
    x = jnp.concatenate([lo, hi], axis)
    lanes = live.shape[0]
    st = status.reshape(lanes, -1)
    cnt = jnp.stack([jnp.sum(st == s, axis=1) for s in range(3)], axis=1)
    return jax.lax.bitcast_convert_type(x, jnp.float32), cnt * live[:, None]


def empty_slots(cfg, lanes: int) -> dict:
    """Zero state planes (all-zero words are a valid codeword) for one
    state layer: the lane cache's entry at a state position."""
    g = cfg.n_groups
    h = cfg.d_inner // cfg.ssm_head_dim
    conv = cfg.d_inner + 2 * cfg.d_state
    ssm = (g, lanes, h, cfg.ssm_head_dim // 2, cfg.d_state)
    cv = (g, lanes, cfg.d_conv - 1, conv // 2)
    u32 = lambda s: jnp.zeros(s, jnp.uint32)
    return {
        "ssm_lo": u32(ssm), "ssm_hi": u32(ssm), "ssm_par": jnp.zeros(ssm, jnp.uint8),
        "conv_lo": u32(cv), "conv_hi": u32(cv), "conv_par": jnp.zeros(cv, jnp.uint8),
    }


def seal(cache, cfg) -> dict:
    """A lane cache (lm.init_cache) with every state entry as zero planes."""
    out = dict(cache)
    for j in positions(cfg):
        lanes = cache[f"p{j}"]["ssm"].shape[1]
        out[f"p{j}"] = empty_slots(cfg, lanes)
    return out


def commit(cache, cachem, lanes, *, cfg):
    """Encode each row of a prefilled batch cache's float state into the
    slot of its lane, ``lanes`` (rows,), in the lane cache: the admission's
    state write. A lane index past the last lane drops its row."""
    out = dict(cache)
    for j in positions(cfg):
        key = f"p{j}"
        ssm = cachem[key]["ssm"]  # (g, rows, H, P, N)
        conv = cachem[key]["conv"]  # (g, rows, K-1, C)
        slot = dict(cache[key])
        for prefix, x, axis in (("ssm", ssm, 3), ("conv", conv, 3)):
            for name, plane in zip(("lo", "hi", "par"), _pack(x, axis)):
                k = f"{prefix}_{name}"
                slot[k] = slot[k].at[:, lanes].set(plane, mode="drop")
        out[key] = slot
    return out


def arm(cache, live, cfg) -> dict:
    """Open a decode block: each state layer gets the lanes' live mask and
    a zeroed (lanes, 3) counter row, both on the groups axis."""
    out = dict(cache)
    g = cfg.n_groups
    lanes = live.shape[0]
    for j in positions(cfg):
        out[f"p{j}"] = dict(
            cache[f"p{j}"],
            live=jnp.broadcast_to(live.astype(jnp.int32), (g, lanes)),
            cnt=jnp.zeros((g, lanes, 3), jnp.int32),
        )
    return out


def harvest(cache, cfg):
    """Close a decode block: strip the live masks and counters, and return
    the block's (lanes, 3) clean / corrected / detected counts."""
    out = dict(cache)
    total = 0
    for j in positions(cfg):
        slot = dict(cache[f"p{j}"])
        slot.pop("live")
        total = total + jnp.sum(slot.pop("cnt"), axis=0)
        out[f"p{j}"] = slot
    return out, total


def open_conv(slots, live):
    """Decoded conv tails (lanes, K-1, C) and their (lanes, 3) counts."""
    return _unpack(slots["conv_lo"], slots["conv_hi"], slots["conv_par"], live, 2)


def seal_conv(slots, tail, live) -> dict:
    """Re-encode new conv tails into the live lanes' slots."""
    out = dict(slots)
    keep = live.reshape(-1, 1, 1) > 0
    for name, plane in zip(("conv_lo", "conv_hi", "conv_par"), _pack(tail, 2)):
        out[name] = jnp.where(keep, plane, slots[name])
    return out
