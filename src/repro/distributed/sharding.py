"""Per-architecture sharding rules: param/activation/cache PartitionSpecs.

Parallelism mapping (DESIGN.md SS6):
  * ``model`` axis: tensor parallelism (Megatron column/row) for attention
    and MLPs, expert parallelism for MoE, channel parallelism for Mamba
    (zero-collective inside the recurrence), vocab parallelism for the
    embedding/head where divisible;
  * ``data`` axis: batch DP + FSDP-style parameter/optimizer sharding
    (gather-on-use is GSPMD's job once the at-rest spec says so);
  * ``pod`` axis (multi-pod): pure DP — gradients reduce hierarchically
    (reduce-scatter intra-pod over ICI, all-reduce inter-pod over DCI).

Rules are name-based over the param tree; anything unknown stays
replicated, which is always correct and shows up as memory in the dry-run
(i.e. loudly).  kv/vocab axes fall back to replication when not divisible
by the tp size (e.g. gemma2 kv=4, hubert vocab=504).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro.configs.base import ArchConfig, ShapeConfig


def shard_map_compat(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the
    search and MoE steps mix replicated and per-shard values freely)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class AxisRules:
    fsdp: str | None = "data"
    tp: str | None = "model"
    ep: str | None = "model"
    dp: tuple[str, ...] = ("data",)     # batch axes (('pod','data') multi-pod)

    @staticmethod
    def for_mesh(mesh: Mesh) -> "AxisRules":
        if "pod" in mesh.axis_names:
            return AxisRules(dp=("pod", "data"))
        return AxisRules()


def _path_names(path) -> list[str]:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(f"[{k.idx}]")
    return out


def _axis_size(mesh: Mesh, name: str | None) -> int:
    if name is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def param_spec(
    cfg: ArchConfig, mesh: Mesh, rules: AxisRules, path, leaf,
    *, serve: bool = False,
) -> P:
    """Param PartitionSpec.  ``serve=True`` switches to weight-stationary
    inference sharding (SSPerf hillclimb 2): weights live TP-sharded over
    ``model`` only and are *never* gathered — at decode the ``data`` axis
    carries batch, so FSDP-on-data weights would be all-gathered every
    step, costing more wire than the whole step's compute."""
    names = _path_names(path)
    name = names[-1]
    tp = rules.tp if _axis_size(mesh, rules.tp) > 1 else None
    fsdp = rules.fsdp if _axis_size(mesh, rules.fsdp) > 1 else None
    if serve:
        fsdp = None          # weight-stationary: no gather-on-use sharding
    ep = rules.ep if _axis_size(mesh, rules.ep) > 1 else None
    tp_size = _axis_size(mesh, rules.tp)
    kv_ok = cfg.n_kv_heads % max(tp_size, 1) == 0
    vocab_ok = cfg.vocab % max(tp_size, 1) == 0
    in_moe = "moe" in names

    if name in ("wq",):
        spec = (fsdp, tp)
    elif name in ("wk", "wv"):
        spec = (fsdp, tp if kv_ok else None)
    elif name in ("wi", "wg"):
        spec = (ep, fsdp, None) if in_moe else (fsdp, tp)
    elif name == "wo":
        spec = (ep, None, fsdp) if in_moe else (tp, fsdp)
    elif name == "in_proj":
        spec = (fsdp, tp)
    elif name == "out_proj":
        spec = (tp, fsdp)
    elif name == "x_proj":
        spec = (tp, None)
    elif name == "dt_proj":
        spec = (None, tp)
    elif name == "A_log":
        spec = (tp, None)
    elif name == "conv_w":
        spec = (None, tp)
    elif name in ("D", "dt_bias", "conv_b"):
        spec = (tp,)
    elif name == "router":
        spec = (fsdp, None)
    elif name == "embed":
        spec = (tp if vocab_ok else None, fsdp)
    elif name == "head":
        spec = (fsdp, tp if vocab_ok else None)
    elif name == "bq":
        spec = (tp,)
    elif name in ("bk", "bv"):
        spec = (tp if kv_ok else None,)
    else:  # norms and anything unrecognised: replicated
        spec = (None,) * leaf.ndim
    if leaf.ndim == len(spec) + 1:      # stacked scan leaf: leading repeat axis
        spec = (None,) + spec
    assert leaf.ndim == len(spec), (names, leaf.shape, spec)
    # drop specs on axes whose size does not divide the dimension
    fixed = []
    for dim, ax in zip(leaf.shape, spec):
        if ax is None:
            fixed.append(None)
        else:
            sz = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                sz *= _axis_size(mesh, a)
            fixed.append(ax if dim % sz == 0 else None)
    return P(*fixed)


def param_shardings(
    cfg: ArchConfig, mesh: Mesh, rules: AxisRules, params_tree: Any,
    *, serve: bool = False,
) -> Any:
    """NamedSharding pytree matching ``params_tree`` (arrays or shape structs)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: NamedSharding(
            mesh, param_spec(cfg, mesh, rules, p, a, serve=serve)
        ),
        params_tree,
    )


def batch_specs(
    cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh, rules: AxisRules
) -> dict[str, P]:
    """PartitionSpecs for one input batch of the given shape cell."""
    dp_size = 1
    for a in rules.dp:
        dp_size *= _axis_size(mesh, a)
    b_ok = shape.global_batch % dp_size == 0 and shape.global_batch >= dp_size
    bspec = rules.dp if b_ok else None
    specs: dict[str, P] = {}
    if cfg.embed_inputs:
        specs["tokens"] = P(bspec, None)
    else:
        specs["frames"] = P(bspec, None, None)
    if shape.kind == "train":
        specs["labels"] = P(bspec, None)
    if cfg.vision_prefix:
        specs["vision_embeds"] = P(bspec, None, None)
        specs["positions"] = P(bspec, None, None)
    return specs


def cache_shardings(
    cfg: ArchConfig,
    mesh: Mesh,
    rules: AxisRules,
    cache_tree: Any,
    *,
    batch: int,
) -> Any:
    """Cache specs: batch-shard KV when divisible, else shard the sequence
    axis over the data axes (long-context decode); SSM channels over tp."""
    dp_size = 1
    for a in rules.dp:
        dp_size *= _axis_size(mesh, a)
    b_ok = batch % dp_size == 0 and batch >= dp_size
    tp = rules.tp if _axis_size(mesh, rules.tp) > 1 else None
    tp_size = _axis_size(mesh, rules.tp)
    kv_ok = cfg.n_kv_heads % max(tp_size, 1) == 0
    din_ok = cfg.d_inner_ % max(tp_size, 1) == 0

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1]
        stacked = leaf.ndim and "scan" in names
        if name in ("k", "v"):
            # decision table: batch over dp when divisible; kv heads over tp
            # when divisible, else the *sequence* axis takes the tp (and,
            # for batch=1 long-context, also the dp) shards — GSPMD handles
            # the partial-softmax combine (sequence-parallel attention).
            if b_ok and kv_ok:
                base = (rules.dp, None, tp, None)
            elif b_ok:
                base = (rules.dp, tp, None, None)
            elif kv_ok:
                base = (None, rules.dp, tp, None)
            else:
                base = (None, tuple(rules.dp) + ((tp,) if tp else ()), None, None)
        elif name == "h":
            base = ((rules.dp, tp if din_ok else None, None)
                    if b_ok else (None, tp if din_ok else None, None))
        elif name == "conv":
            base = ((rules.dp, None, tp if din_ok else None)
                    if b_ok else (None, None, tp if din_ok else None))
        else:
            base = (None,) * leaf.ndim
        if leaf.ndim == len(base) + 1:
            base = (None,) + base
        return NamedSharding(mesh, P(*base))

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)


def activation_spec(cfg: ArchConfig, rules: AxisRules, batch_ok: bool = True) -> P:
    """Residual-stream constraint: batch over dp; d_model over tp for the
    very wide archs (keeps the scan carry within HBM, DESIGN.md SS6)."""
    b = rules.dp if batch_ok else None
    d = rules.tp if cfg.d_model >= 8192 else None
    return P(b, None, d)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Activation-constraint helper threaded through the model layers.

    ``con(x, roles...)`` applies ``with_sharding_constraint`` where each
    role is None, "dp" (batch axes) or "tp" (tensor axis); a role is
    silently dropped when the dimension is not divisible by the axis size,
    so the same model code serves every arch (gemma2's kv=4 heads, hubert's
    504-vocab head, long_500k's batch=1 all degrade to replication instead
    of erroring).  mesh=None makes every call a no-op (unit tests).
    """

    mesh: Mesh | None = None
    dp: tuple[str, ...] = ("data",)
    tp: str = "model"
    seq_shard: bool = False   # Megatron-SP: residual stream S over tp

    def _size(self, axes) -> int:
        n = 1
        for a in axes if isinstance(axes, tuple) else (axes,):
            n *= _axis_size(self.mesh, a)
        return n

    def con(self, x, *roles):
        if self.mesh is None:
            return x
        assert x.ndim == len(roles), (x.shape, roles)
        spec = []
        for dim, role in zip(x.shape, roles):
            if role == "sp":   # sequence-parallel residual (SSPerf A3)
                role = "tp" if self.seq_shard else None
            if role == "dp" and dim % max(self._size(self.dp), 1) == 0 and self._size(self.dp) > 1:
                spec.append(self.dp)
            elif role == "tp" and dim % max(self._size(self.tp), 1) == 0 and self._size(self.tp) > 1:
                spec.append(self.tp)
            else:
                spec.append(None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )
