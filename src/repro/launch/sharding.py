"""Parameter / batch / cache sharding rules (DESIGN.md §6).

Tensor-parallel ("model" axis): attention heads, d_ff, MoE experts, mamba
d_inner/heads, vocab of embed/lm_head.
FSDP ("data" axis, + "pod" on the multi-pod mesh): the other large axis of
every big matrix, so params/grads/optimizer state scale down with the full
data-parallel world (ZeRO-3 style; XLA inserts the all-gathers).

Rules are matched on the '/'-joined pytree path; specs apply to the TRAILING
dims of the leaf so stacked block params ([n_blocks, ...]) get a leading None
automatically.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FS = "__FSDP__"          # placeholder replaced by the mesh's fsdp axes

_RULES: Sequence[Tuple[str, tuple]] = (
    # MoE experts [E, D, F] / [E, F, D]: experts over model, D over fsdp
    (r"ffn/(wg|wu)$",        ("model", FS, None)),
    (r"ffn/wd$",             ("model", None, FS)),
    (r"router$",             (None, None)),
    # shared expert + dense MLP [D, F] / [F, D]
    (r"(shared|ffn|mlp)/(wg|wu)/w$", (FS, "model")),
    (r"(shared|ffn|mlp)/wd/w$",      ("model", FS)),
    # attention
    (r"(wq|wk|wv)/w$",       (FS, "model")),
    (r"(wq|wk|wv)/b$",       ("model",)),
    (r"wo/w$",               ("model", FS)),
    (r"wo/b$",               (None,)),
    # mamba2
    (r"(wz|wx|wdt)$",        (FS, "model")),
    (r"(wB|wC)$",            (FS, None)),
    (r"conv_x$",             (None, "model")),
    (r"conv_bx$",            ("model",)),
    (r"(conv_B|conv_C)$",    (None, None)),
    (r"mixer/norm$",         ("model",)),
    (r"out_proj$",           ("model", FS)),
    # decision-fusion heads (small)
    (r"(vision|audio_head)/(proj|w1)$", (None, None)),
    (r"(vision|audio_head)/w2$",        (None, "model")),
    # embeddings
    (r"lm_head$",            (FS, "model")),
    (r"embed$",              ("model", FS)),
)


def _resolve(spec: tuple, fsdp: Optional[tuple]) -> tuple:
    # a singleton fsdp axis collapses to its bare name: P("data") and
    # P(("data",)) shard identically but do not compare equal as specs
    if fsdp is not None and len(fsdp) == 1:
        fsdp = fsdp[0]
    return tuple((fsdp if s == FS else s) for s in spec)


def param_pspec(path: str, ndim: int, fsdp: Optional[tuple]) -> P:
    for pat, spec in _RULES:
        if re.search(pat, path):
            spec = _resolve(spec, fsdp)
            spec = spec[:ndim]
            pad = ndim - len(spec)
            return P(*((None,) * pad + tuple(spec)))
    return P(*((None,) * ndim))        # replicate (norms, scalars, biases)


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(p.key) if hasattr(p, "key") else f"#{getattr(p, 'idx', p)}")
    return "/".join(parts)


def _axis_prod(mesh, ax) -> int:
    names = ax if isinstance(ax, tuple) else (ax,)
    return int(np.prod([mesh.shape[n] for n in names]))


def sanitize_pspec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on any dim whose size is not divisible by the mesh axes
    (pjit requires exact divisibility of explicitly-sharded inputs; e.g.
    GQA kv=8 heads cannot shard over model=16, whisper's 51865 vocab cannot
    shard over 16).  Dropped dims are recorded replicated."""
    dims = []
    for d in range(len(shape)):
        ax = spec[d] if d < len(spec) else None
        if ax is None:
            dims.append(None)
            continue
        dims.append(ax if shape[d] % _axis_prod(mesh, ax) == 0 else None)
    return P(*dims)


def sanitize_tree(pspecs, tree, mesh: Mesh):
    return jax.tree.map(
        lambda s, l: sanitize_pspec(s, l.shape, mesh), pspecs, tree)


def tree_pspecs(tree, fsdp: Optional[tuple], mesh: Optional[Mesh] = None):
    """PartitionSpec pytree matching `tree` (works on ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = [param_pspec(_path_str(path), np.ndim(leaf) if not hasattr(leaf, "ndim")
             else leaf.ndim, fsdp) for path, leaf in flat]
    out = jax.tree_util.tree_unflatten(treedef, specs)
    if mesh is not None:
        out = sanitize_tree(out, tree, mesh)
    return out


def tree_shardings(tree, mesh: Mesh, fsdp: Optional[tuple]):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_pspecs(tree, fsdp))


def serving_buffer_shardings(bufs, mesh: Mesh):
    """Shardings for the flat serving param buffers (launch/parambuf).

    Decode reads the whole parameter set every step, and the flat layout
    erases the per-tensor axes the `_RULES` table keys on — so the buffers
    are REPLICATED across the mesh: every device holds a full copy and a
    round-boundary hot-swap is one donated copy per device, no collective
    on the decode critical path."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), bufs)


# ---------------------------------------------------------------------------
# scenario sweeps: shard an embarrassingly-parallel grid's leading axis
# ---------------------------------------------------------------------------
def scenario_shard_map(fn, mesh: Mesh, n_args: int,
                       sharded_args: Sequence[int] = (0,)):
    """Wrap an already-vmapped sweep ``fn`` in ``shard_map`` over the mesh's
    ``"scenario"`` axis: arguments listed in ``sharded_args`` are split along
    their leading (scenario) axis, the rest are replicated, and every output
    leaf must carry a leading scenario axis.  Scenarios are independent whole
    programs (no cross-scenario collectives), so this is pure SPMD fan-out —
    wall-clock divides by the device count.  Pad the grid first
    (``pad_leading_axis``) when it doesn't divide the mesh."""
    sharded = set(sharded_args)
    in_specs = tuple(P("scenario") if i in sharded else P()
                     for i in range(n_args))
    # check_vma=False: the varying-axes checker mis-types lax.scan carries
    # that mix replicated and sharded leaves; the sweeps are
    # collective-free, so the check buys nothing here
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P("scenario"), check_vma=False)


# ---------------------------------------------------------------------------
# population sweeps: logical axis rules for the 2-D ("scenario", "clients")
# mesh.  MaxText-style indirection — callers name the LOGICAL axes of each
# tensor ("which axis is the client axis?") and the rules table maps them to
# mesh axes, so the round program never hard-codes a mesh layout and a rule
# absent from the mesh degrades to replication.
# ---------------------------------------------------------------------------
SWEEP_AXIS_RULES: Sequence[Tuple[str, Optional[str]]] = (
    ("scenario", "scenario"),   # grid rows — independent whole experiments
    ("clients", "clients"),     # population axis of the client store / xs
    ("rounds", None),           # the lax.scan axis — never sharded
    ("batch", None),            # per-client samples — never sharded
)


def logical_pspec(axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None,
                  rules=SWEEP_AXIS_RULES) -> P:
    """PartitionSpec for a tensor whose dims carry the given logical axis
    names (None = unnamed/replicated dim).  Names missing from the rules
    table, mapped to None, or mapped to an axis the ``mesh`` doesn't carry
    all resolve to replication — the same program runs on a 1-D
    ``("scenario",)`` mesh with the client axis silently unsharded."""
    table = dict(rules)
    dims = []
    for ax in axes:
        mesh_ax = table.get(ax) if ax is not None else None
        if (mesh is not None and mesh_ax is not None
                and mesh_ax not in mesh.axis_names):
            mesh_ax = None
        dims.append(mesh_ax)
    return P(*dims)


def population_shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` over the 2-D ``("scenario", "clients")`` mesh with
    explicit per-argument (pytree) specs — unlike ``scenario_shard_map``'s
    uniform leading-axis split, population sweeps shard different arguments
    along different axes: the V grid over "scenario", the client store and
    per-client randomness over "clients", the carry replicated.
    check_vma=False for the same scan-carry reason as ``scenario_shard_map``;
    the only collectives are the cohort gather's psums / all_gathers over
    "clients", whose outputs are replicated by construction."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pad_leading_axis(tree, multiple: int):
    """Pad every leaf's leading axis to a multiple of ``multiple`` by
    repeating the last scenario (duplicate work, dropped by
    ``slice_leading_axis`` — never garbage values, so padded rows still
    execute the real program)."""
    import jax.numpy as jnp

    def pad(x):
        n = (-x.shape[0]) % multiple
        if n == 0:
            return x
        reps = jnp.broadcast_to(x[-1:], (n,) + x.shape[1:])
        return jnp.concatenate([jnp.asarray(x), reps])

    return jax.tree.map(pad, tree)


def slice_leading_axis(tree, n: int):
    """Drop the rows ``pad_leading_axis`` added."""
    return jax.tree.map(lambda x: x[:n], tree)


# ---------------------------------------------------------------------------
# optimizer state: same layout as the matching parameter
# ---------------------------------------------------------------------------
def opt_state_pspecs(opt_state_shape, params_shape, fsdp: Optional[tuple]):
    """Optimizer-state specs built structurally from the parameter specs:
    adam m/v mirror the parameter layout; adafactor row stats drop the last
    param dim, col stats the second-last; scalars replicate."""
    pspecs = tree_pspecs(params_shape, fsdp)

    def factored(spec_and_shape):
        spec, leaf = spec_and_shape
        s = tuple(spec)
        if leaf.ndim >= 2:
            return {"r": P(*s[:-1]), "c": P(*(s[:-2] + (s[-1],)))}
        return {"v": P(*s)}

    out = {}
    for key, sub in opt_state_shape.items():
        if key == "step":
            out[key] = P()
        elif key in ("m", "v"):
            out[key] = pspecs
        elif key == "f":
            out[key] = jax.tree.map(
                lambda spec, leaf: factored((spec, leaf)), pspecs, params_shape)
        else:
            out[key] = jax.tree.map(lambda _: P(), sub)
    return out
