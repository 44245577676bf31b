"""Sharding rules: parameter PartitionSpecs + activation constraints.

Megatron-style TP assignment by parameter name (column-parallel up
projections, row-parallel down projections, vocab-parallel embeddings,
expert-parallel MoE weights), DP over (pod, data), optional sequence
parallelism for activations.  All specs go through GSPMD (jit in/out
shardings), so non-divisible dimensions are legal (padded internally);
the rules still prefer divisible choices where the config allows.

Also home to the *serving* mesh helpers (``make_batch_mesh`` /
``batch_shard_spec``): the solver front-door shards micro-batched request
groups over a 1-D batch axis — the pure data-parallel limit of the rules
above, kept here so training and serving agree on mesh construction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

SERVE_BATCH_AXIS = "batch"


def make_batch_mesh(num_devices: int | None = None,
                    axis: str = SERVE_BATCH_AXIS) -> Mesh:
    """1-D device mesh for sharded batch serving (``QRServer(mesh=...)``).

    ``num_devices=None`` takes every visible device.  On CPU hosts, fake
    devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (must be set before jax initializes).  Flushed request groups are padded
    to a multiple of ``num_devices x block_b`` and split over ``axis`` — see
    ``repro.solvers.qr_update.qr_append_rows_batched``.
    """
    avail = jax.device_count()
    n = avail if num_devices is None else num_devices
    if n > avail:
        raise ValueError(
            f"requested a {n}-device batch mesh but only {avail} devices are "
            f"visible (on CPU set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before importing jax)")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def batch_shard_spec(ndim: int, axis: str = SERVE_BATCH_AXIS) -> P:
    """PartitionSpec sharding dim 0 (the stacked-request dim) over ``axis``."""
    return P(axis, *([None] * (ndim - 1)))


@dataclasses.dataclass(frozen=True)
class MeshRules:
    mesh: Mesh
    model_axis: str = "model"
    sequence_parallel: bool = False
    fsdp: bool = False  # additionally shard params over the data axes (ZeRO-3)

    @property
    def data_axes(self):
        return tuple(n for n in self.mesh.axis_names if n != self.model_axis)

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


# column-parallel (shard OUTPUT dim over model)
_COL = {"wq", "wk", "wv", "w1", "w3", "wup", "wqkv", "in_proj", "wgate",
        "frame_proj", "vision_proj", "lm_head", "wx", "wh"}
# row-parallel (shard INPUT dim over model)
_ROW = {"wo", "w2", "wdown", "out_proj"}
# replicated small params
_REP = {"scale", "A_log", "D", "dt_bias", "conv_w"}


def _rule_for(name: str, ndim_base: int, cfg, model_axis: str, model_size: int):
    if name in _REP:
        return P(*([None] * ndim_base))
    if name == "embed":
        return P(model_axis, None)  # vocab-parallel
    if name == "router":
        return P(None, None)
    if name in ("w1", "w2", "w3") and ndim_base == 3:  # MoE expert weights
        # expert-parallel when experts divide the axis, else TP on d_ff
        if cfg.n_experts and cfg.n_experts % max(model_size, 1) == 0:
            return P(model_axis, None, None)
        if name == "w2":
            return P(None, model_axis, None)
        return P(None, None, model_axis)
    if name in _COL:
        return P(*([None] * (ndim_base - 1)), model_axis)
    if name in _ROW:
        return P(model_axis, *([None] * (ndim_base - 1)))
    return P(*([None] * ndim_base))


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop sharding on any dim the mesh axes don't evenly divide — explicit
    input shardings must divide exactly (GSPMD pads only intermediates)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if shape[i] % size == 0 else None)
    return P(*out)


def param_pspecs(params, cfg, rules: MeshRules):
    """PartitionSpec pytree matching ``params``; scanned stacks get a leading
    None for every extra (layer/group) dimension."""

    def spec_of(path, leaf):
        name = None
        for p in reversed(path):
            if isinstance(p, jax.tree_util.DictKey):
                name = p.key
                break
        base = _base_ndim(name, leaf)
        rule = _rule_for(name, base, cfg, rules.model_axis, rules.model_size)
        extra = leaf.ndim - base
        if extra > 0:
            rule = P(*([None] * extra), *rule)
        rule = sanitize_spec(rule, leaf.shape, rules.mesh)
        if rules.fsdp and leaf.ndim >= 2:
            rule = add_dp_axis(rule, leaf.shape, rules)
        return rule

    return jax.tree_util.tree_map_with_path(spec_of, params)


def add_dp_axis(spec: P, shape, rules: MeshRules) -> P:
    """ZeRO-style: put the data axes on the first free, divisible dim.

    With params sharded this way GSPMD all-gathers each layer's weights just
    before use and reduce-scatters their gradients — FSDP semantics from
    sharding annotations alone."""
    dp = rules.data_axes if len(rules.data_axes) > 1 else rules.data_axes[0]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for d in range(len(shape)):
        if entries[d] is None and shape[d] % rules.dp_size == 0 and shape[d] >= rules.dp_size:
            entries[d] = dp
            return P(*entries)
    return spec


def _base_ndim(name: str, leaf) -> int:
    if name in _REP:
        return 1 if name in ("scale", "A_log", "D", "dt_bias") else 2
    if name in ("w1", "w2", "w3") and leaf.ndim >= 3:
        return 3  # MoE (E, d, f); dense w1/w2/w3 are 2-D and hit the branch below
    return min(leaf.ndim, 2)


def batch_spec(kind: str, rules: MeshRules) -> P:
    """Input-batch specs: batch over (pod, data)."""
    dp = rules.data_axes
    dp = dp if len(dp) > 1 else dp[0]
    if kind in ("tokens", "labels"):
        return P(dp, None)
    if kind in ("patch_embs", "frames"):
        return P(dp, None, None)
    if kind == "token1":  # decode: (B,)
        return P(dp)
    raise ValueError(kind)


def activation_spec(rules: MeshRules) -> P:
    """Hidden-state constraint between blocks: DP on batch (+ SP on seq)."""
    dp = rules.data_axes
    dp = dp if len(dp) > 1 else dp[0]
    seq = rules.model_axis if rules.sequence_parallel else None
    return P(dp, seq, None)


def cache_pspec(cfg, rules: MeshRules, batch: int):
    """KV-cache / state sharding for decode. Batch over data when divisible,
    else shard the sequence dim (long_500k: batch=1)."""
    dp = rules.data_axes
    dp = dp if len(dp) > 1 else dp[0]
    dp_size = 1
    for a in rules.data_axes:
        dp_size *= rules.mesh.shape[a]
    batch_ok = batch % dp_size == 0 if batch >= dp_size else False

    def spec(path, leaf):
        name = None
        for p in reversed(path):
            if isinstance(p, jax.tree_util.DictKey):
                name = p.key
                break
        nd = leaf.ndim
        if name in ("k", "v", "xk", "xv"):
            # (L, B, S, Hkv, hd): batch over data if possible else seq over data
            if batch_ok:
                sp = P(None, dp, None, rules.model_axis, None)
            else:
                sp = P(None, None, dp, rules.model_axis, None)
        elif name in ("conv", "ssm", "mlstm"):
            # (G, A, B, ...) recurrent states: batch over data when divisible
            sp = P(None, None, dp, *([None] * (nd - 3))) if batch_ok else P(*([None] * nd))
        elif name in ("slstm",):
            sp = P(None, None, dp, None) if batch_ok else P(*([None] * nd))
        else:
            sp = P(*([None] * nd))
        return sanitize_spec(sp, leaf.shape, rules.mesh)

    return spec
