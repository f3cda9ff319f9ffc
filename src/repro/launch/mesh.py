"""Production mesh + sharding rules.

Mesh: `(data=16, model=16)` single pod (256 chips) and
`(pod=2, data=16, model=16)` for the 2-pod 512-chip dry-run.  Defined as
FUNCTIONS so importing this module never touches jax device state.

Sharding policy (the baseline; §Perf hillclimbs tweak it):

* params — FSDP over `data` (ZeRO-3-style: XLA inserts the all-gathers) ×
  tensor-parallel over `model` on the *flat* projection dims (every
  assigned d_model/d_ff is divisible by 16; heads are NOT always, which
  is why rules shard flattened head×head_dim axes — see DESIGN.md §5).
  Pods replicate params (pure DP between pods: gradient all-reduce over
  `pod` only), the standard multi-pod layout given slow cross-pod links.
* optimizer m/v — same spec as their param.
* activations — batch over (`pod`, `data`).
* decode caches — batch over data when divisible; sequence over `data`
  for the B=1 long-context cells; heads/feature dims over `model`.

All rules are divisibility-checked against the actual mesh: a dim is only
sharded if evenly divisible, so every (arch × shape × mesh) cell lowers.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Generic mesh helper (tests/examples use small meshes like (1,1))."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# ---------------------------------------------------------------------------
# rule machinery
# ---------------------------------------------------------------------------
def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return int(mesh.shape[name]) if name in mesh.shape else 0


def _fit(mesh: Mesh, shape: tuple[int, ...], spec: tuple) -> P:
    """Drop axes that don't exist in the mesh or don't divide the dim."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        size = _axis_size(mesh, ax)
        if size <= 1 or dim % size != 0:
            out.append(None)
        else:
            out.append(ax)
    return P(*out)


def dp_axes(mesh: Mesh):
    """The pure-data-parallel axes of this mesh (batch dim sharding)."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def fsdp_axis(mesh: Mesh):
    """Parameter-sharding axis (within-pod FSDP)."""
    return "data"


#: path-pattern -> spec template (matched against '/'-joined tree path).
#: 'F' = fsdp axis placeholder, 'M' = model axis.
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("M", "F")),  # (V, d): vocab-parallel
    (r"head$", ("F", "M")),
    (r"dec_pos$", (None, "M")),
    (r"enc_in$", ("F", "M")),
    # attention
    (r"(wq|wk|wv)$", ("F", "M")),
    (r"(bq|bk|bv)$", ("M",)),
    (r"attn/wo$", ("M", "F")),
    # mlp
    (r"(wi|wg)$", ("F", "M")),
    (r"wo2$", ("M", "F")),
    # moe (E, d, ff) / (E, ff, d); router (d, E)
    (r"router$", ("F", None)),
    (r"moe/(wi|wg)$", (None, "F", "M")),
    (r"moe/wo$", (None, "M", "F")),
    # mamba2
    (r"ssm/(wz|wx)$", ("F", "M")),
    (r"ssm/conv$", (None, "M")),
    (r"ssm/conv_b$", ("M",)),
    (r"ssm/(wB|wC)$", ("F", None)),
    (r"ssm/wdt$", ("F", "M")),
    (r"ssm/norm_y$", ("M",)),
    (r"ssm/out$", ("M", "F")),
    # rwkv6 time-mix / channel-mix
    (r"tm/(wr|wk|wv|wg)$", ("F", "M")),
    (r"tm/wo$", ("M", "F")),
    (r"tm/wA$", ("F", None)),
    (r"tm/wB$", (None, "M")),
    (r"tm/(mu)$", (None, "M")),
    (r"tm/(w0|ln_x)$", ("M",)),
    (r"cm/(wr|wk)$", ("F", "M")),
    (r"cm/wv$", ("M", "F")),
    (r"cm/mu$", (None, "M")),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def param_spec(mesh: Mesh, path, leaf) -> P:
    """PartitionSpec for one parameter leaf.

    Stacked layer dims (leading n_layers/group dims) are never sharded;
    rules apply to the trailing dims that match the rule's arity.
    """
    s = _path_str(path)
    shape = tuple(leaf.shape)
    for pat, template in _PARAM_RULES:
        if re.search(pat, s):
            tmpl = [
                {"F": fsdp_axis(mesh), "M": "model"}.get(a, a) if isinstance(a, str) else a
                for a in template
            ]
            n_lead = len(shape) - len(tmpl)
            if n_lead < 0:
                return P()
            full = (None,) * n_lead + tuple(tmpl)
            return _fit(mesh, shape, full)
    # norms, biases, scalars: replicate
    return P()


def params_shardings(mesh: Mesh, params_shape: Any):
    """Tree of NamedShardings matching a params (shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(mesh, path, leaf)), params_shape
    )


def opt_state_shardings(mesh: Mesh, opt_shape: Any):
    """m/v follow their params; step is replicated."""
    def spec_of(path, leaf):
        s = _path_str(path)
        if s.startswith(("m/", "v/", "master/")):
            sub_path = path[1:]
            return NamedSharding(mesh, param_spec(mesh, sub_path, leaf))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_of, opt_shape)


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------
def batch_shardings(mesh: Mesh, batch_shape: Any):
    """tokens (B, S): batch over dp axes. frames (B, T, d): same."""
    dp = dp_axes(mesh)

    def spec_of(path, leaf):
        shape = tuple(leaf.shape)
        return NamedSharding(mesh, _fit(mesh, shape, (dp,) + (None,) * (len(shape) - 1)))

    return jax.tree_util.tree_map_with_path(spec_of, batch_shape)


def cache_spec(mesh: Mesh, path, leaf, seq_shard: bool = False) -> P:
    """Decode-cache sharding (see module docstring).

    ``seq_shard``: prefer splitting the cache *sequence* over `model`
    when the KV heads don't divide it (flash-decode-style split-S: XLA
    partial-softmaxes over the shards with small combine collectives) —
    the §Perf fix for the involuntary-resharding pathology the baseline
    head_dim sharding triggers.
    """
    s = _path_str(path)
    shape = tuple(leaf.shape)
    dp = dp_axes(mesh)
    if s.endswith("pos"):
        return P()

    def try_spec(spec):
        return _fit(mesh, shape, spec)

    if re.search(r"(^|/)(k|v|self_k|self_v|cross_k|cross_v)$", s):
        # (L, B, S, Hkv, hd): batch over dp; heads over model; if heads
        # don't divide: split-S over model (seq_shard) or head_dim (base);
        # if batch unshardable (B=1 long-context), sequence over data
        spec = try_spec((None, dp, None, "model", None))
        if spec[1] is None:
            spec = try_spec((None, None, "data", "model", None))
            if spec[3] is None:  # few kv heads: shard head_dim
                spec = try_spec((None, None, "data", None, "model"))
        elif spec[3] is None:
            if seq_shard:
                spec = try_spec((None, dp, "model", None, None))
            else:
                spec = try_spec((None, dp, None, None, "model"))
        return spec
    if s.endswith("ssm") or s.endswith("wkv"):
        # (..., B, H, N, P) state: batch over dp, heads over model
        n = len(shape)
        spec = try_spec((None,) * (n - 4) + (dp, "model", None, None))
        if spec[n - 4] is None:
            spec = try_spec((None,) * (n - 4) + (None, "model", "data", None))
        if spec[n - 3] is None:
            spec = try_spec((None,) * (n - 4) + (None, None, "data", "model"))
        return spec
    if s.endswith("conv"):
        # (..., B, K-1, d_in)
        n = len(shape)
        spec = try_spec((None,) * (n - 3) + (dp, None, "model"))
        if spec[n - 3] is None:
            spec = try_spec((None,) * (n - 3) + (None, None, "model"))
        return spec
    if "shift" in s:
        # (L, B, d)
        spec = try_spec((None, dp, "model"))
        if spec[1] is None:
            spec = try_spec((None, None, "model"))
        return spec
    # default: batch over dp on dim 1 if it divides
    if len(shape) >= 2:
        return try_spec((None, dp) + (None,) * (len(shape) - 2))
    return P()


def cache_shardings(mesh: Mesh, cache_shape: Any, seq_shard: bool = False):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, cache_spec(mesh, path, leaf, seq_shard)),
        cache_shape,
    )


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
