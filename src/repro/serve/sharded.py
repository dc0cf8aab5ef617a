"""Mesh-aware serving: plan-table placement policy + sharded step builders.

This is the layer that takes a single-device serving configuration —
params, decode state, and the compressed-activation ``lut_tables`` dict —
and places it on an explicit ``(data, model)`` mesh under a **bit-identity
contract**: the sharded program's logits (and therefore every greedy
token) are bit-for-bit the single-device program's, for every family and
both table backends (asserted by tests/mesh/).  Three pieces:

* **Table placement** (:class:`PlacementPolicy`, :func:`place_tables`) —
  small per-site tables replicate (``NamedSharding(mesh, P())``); large
  stacked ``(L, …)`` slabs shard their *layer* dim along the data axis
  when the layer count divides it, with gather-at-use: the evaluators
  already index the stack with ``jnp.take`` on the (traced) layer id, so
  GSPMD inserts the gather exactly where the slab is consumed.  Layer
  sharding is exact — tables are integer data and no float reduction
  crosses the split.

* **Param/state placement** (:func:`serve_param_shardings`,
  :func:`serve_cache_shardings`) — weights are tensor-parallel *at rest*
  (every "tp" axis from ``param_defs`` kept, 1/|model| memory per
  device) and gathered at step entry: sharded float *compute* is not
  bit-stable on this backend — XLA picks reduction and vectorization
  strategies per shape, so even an elementwise ``silu`` on a half-width
  shard can differ by an ulp — and an all-gather is bitwise-lossless, so
  gathering weights and computing at single-device shapes is the only
  placement that is exact by construction.  The one sharded-compute
  exception is the MoE expert stacks: each expert's GEMM shape is
  identical sharded or not, and the combine adds disjoint contributions
  in expert order (the same order the single-device scatter-add uses).
  The KV/recurrent decode state shards over the batch (data) axis only.

* **Step builders** (:class:`ShardedServe`) — jitted prefill / decode /
  replay wrappers running under :func:`repro.nn.sharding.exact_tp`, in
  one of two modes: ``"gspmd"`` (the default; one ``jax.jit`` whose
  sharding constraints drive the partitioner) or ``"shard_map"`` (a
  top-level ``shard_map`` manual over *every* mesh axis — the fully
  manual region where ``layer_scan`` keeps ``lax.scan`` instead of
  python-unrolling).  In both modes the table arrays are threaded in as
  explicit operands rather than closures, so their committed placement
  (and any per-device buffer divergence) is what the program actually
  reads.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import ArchConfig
from repro.nn.sharding import (
    DP_AXES,
    TP_AXIS,
    exact_tp,
    manual_axes,
    named_sharding,
    use_mesh,
)


# =========================================================================
# table placement
# =========================================================================
@dataclasses.dataclass(frozen=True)
class PlacementPolicy:
    """When to shard a stacked ``(L, …)`` table slab instead of
    replicating it.

    ``shard_threshold_bytes``: stacks below this replicate — the gather
    they'd save is worth less than the per-use collective.
    ``layer_axis``: mesh axis the layer dim shards over (the data axis —
    the model axis stays free for expert/tensor parallelism).
    """

    shard_threshold_bytes: int = 1 << 20
    layer_axis: str = "data"


def _arrays_nbytes(tree) -> int:
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree.leaves(tree) if hasattr(a, "dtype"))


def _entry_placement(entry: dict, mesh, policy: PlacementPolicy):
    """-> (placement label, total bytes, per-device bytes)."""
    n_bytes = _arrays_nbytes(entry)
    if "stacked" in entry and mesh is not None:
        n_layers = entry["stacked"]["meta"]["n_layers"]
        n_axis = int(mesh.shape.get(policy.layer_axis, 1))
        if (n_axis > 1 and n_bytes >= policy.shard_threshold_bytes
                and n_layers % n_axis == 0):
            return "layer_sharded", n_bytes, -(-n_bytes // n_axis)
    return "replicated", n_bytes, n_bytes


def place_tables(lut_tables: dict | None, mesh,
                 policy: PlacementPolicy | None = None):
    """Device-put every table array per the placement policy.

    Returns ``(placed_tables, report)`` — the same-structure dict with
    committed arrays, and a per-site report
    ``{site: {"placement", "bytes", "per_device_bytes"}}``.  With no mesh
    the tables pass through untouched.
    """
    if lut_tables is None or mesh is None:
        return lut_tables, {}
    policy = policy or PlacementPolicy()
    rep = NamedSharding(mesh, P())

    def put(tree, sharding):
        return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)

    report: dict[str, dict] = {}
    sites: dict[str, dict] = {}
    for site, entry in lut_tables.get("sites", {}).items():
        placement, n_bytes, per_dev = _entry_placement(entry, mesh, policy)
        report[site] = {"placement": placement, "bytes": n_bytes,
                        "per_device_bytes": per_dev}
        if placement == "layer_sharded":
            st = entry["stacked"]
            layer_sh = NamedSharding(mesh, P(policy.layer_axis))
            sites[site] = {"stacked": {
                "meta": st["meta"],
                "arrays": put(st["arrays"], layer_sh),
                "meta_i": jax.device_put(st["meta_i"], layer_sh),
                "meta_f": jax.device_put(st["meta_f"], layer_sh),
            }}
        elif "stacked" in entry:
            st = entry["stacked"]
            sites[site] = {"stacked": {
                "meta": st["meta"],
                "arrays": put(st["arrays"], rep),
                "meta_i": jax.device_put(st["meta_i"], rep),
                "meta_f": jax.device_put(st["meta_f"], rep),
            }}
        elif "layers" in entry:
            sites[site] = {"layers": [
                {"meta": e["meta"], "arrays": put(e["arrays"], rep)}
                for e in entry["layers"]]}
        else:
            sites[site] = {"meta": entry["meta"],
                           "arrays": put(entry["arrays"], rep)}
    placed = dict(lut_tables)
    placed["sites"] = sites
    return placed, report


def plan_placement_report(lut_tables: dict | None, mesh,
                          policy: PlacementPolicy | None = None) -> dict:
    """Placement accounting without moving any data (dry-run sizing):
    per-site decisions plus replicated / layer-sharded / per-device byte
    totals for the given mesh."""
    if not lut_tables:
        return {"sites": {}, "replicated_bytes": 0, "sharded_bytes": 0,
                "per_device_bytes": 0}
    policy = policy or PlacementPolicy()
    sites = {}
    rep_b = shard_b = per_dev = 0
    for site, entry in lut_tables.get("sites", {}).items():
        placement, n_bytes, pd = _entry_placement(entry, mesh, policy)
        sites[site] = {"placement": placement, "bytes": n_bytes,
                       "per_device_bytes": pd}
        per_dev += pd
        if placement == "layer_sharded":
            shard_b += n_bytes
        else:
            rep_b += n_bytes
    return {"sites": sites, "replicated_bytes": rep_b,
            "sharded_bytes": shard_b, "per_device_bytes": per_dev}


# =========================================================================
# param / state placement (bit-exact serving)
# =========================================================================
# Expert-parallel weight stacks: "tp" sits on the expert dim, which is
# exact to shard (each expert's GEMM is local to one shard).
_EXPERT_PARAMS = ("moe_w_in", "moe_w_out")


def serve_param_shardings(cfg: ArchConfig, mesh):
    """At-rest NamedShardings for bit-exact sharded serving.

    fsdp is dropped (no ZeRO-3 gathers on the decode path, as in
    ``param_specs(fsdp=False)``); every "tp" axis from the model's
    ``param_defs`` is kept, so big weights cost 1/|model| memory per
    device.  Exactness does NOT ride on these axes: at step entry the
    serving program re-constrains every non-expert weight to replicated
    (one all-gather, bitwise-lossless), so all float math runs at
    single-device shapes — sharded *compute* is not bit-stable on this
    backend even for elementwise transcendentals (XLA picks
    vectorization strategies per shape), so only the disjoint
    expert-parallel MoE GEMMs, whose per-expert shapes are identical
    either way, stay sharded through the compute.
    """
    from repro.nn.transformer import ParamDef, param_defs

    defs = param_defs(cfg)

    def resolve(path, d: ParamDef):
        axes = [None if a == "fsdp" else a for a in d.axes]
        return named_sharding(mesh, *axes, shape=d.shape)

    return jax.tree_util.tree_map_with_path(
        resolve, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def _state_axes(path, leaf) -> tuple:
    """Logical axes for one decode-state leaf: batch over dp only (the
    sequence dim must not shard — splitting the attention reduction over
    the model axis would reorder the softmax/PV float sums)."""
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    nd = len(leaf.shape)
    if name in ("k", "v", "xk", "xv"):           # (L|G, B, T, KV, Dh)
        return (None, "dp", None, None, None)
    if name in ("k_scale", "v_scale"):           # (L, B, T, KV)
        return (None, "dp", None, None)
    if name == "wkv":                            # (L, B, H, N, N)
        return (None, "dp", None, None, None)
    if name in ("att_x", "ffn_x"):               # (L, B, 1, d)
        return (None, "dp", None, None)
    if name == "conv":                           # (..., B, K-1, drnn)
        return (None,) * (nd - 3) + ("dp", None, None)
    if name == "lru":                            # (..., B, drnn)
        return (None,) * (nd - 2) + ("dp", None)
    return (None,) * nd


def serve_cache_shardings(cfg: ArchConfig, mesh, batch: int, max_seq: int,
                          kv_dtype: str = "bfloat16"):
    """Batch-over-dp-only NamedShardings matching ``cache_specs``."""
    from .kvcache import cache_specs

    specs = cache_specs(cfg, batch, max_seq, kv_dtype)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: named_sharding(mesh, *_state_axes(path, leaf),
                                          shape=leaf.shape),
        specs)


def batch_placement(mesh, batch: dict) -> dict:
    """Device-put a prefill batch dict with dim 0 (requests) over dp."""
    return {
        k: jax.device_put(
            jnp.asarray(v),
            named_sharding(mesh, "dp", *(None,) * (jnp.asarray(v).ndim - 1),
                           shape=jnp.asarray(v).shape))
        for k, v in batch.items()
    }


# =========================================================================
# table operand split (manual mode threads arrays explicitly)
# =========================================================================
_ARR = "__table_arr__"


def split_table_operands(tables: dict | None):
    """Split a ``lut_tables`` dict into ``(array_leaves, rebuild)``.

    A manual ``shard_map`` region must receive the table slabs as
    explicit mapped operands — closures are reserved for statics.  The
    python-scalar metas stay in the template; ``rebuild(leaves)``
    reassembles the exact dict inside the region.
    """
    leaves: list = []

    def walk(obj):
        if hasattr(obj, "dtype") and hasattr(obj, "shape"):
            leaves.append(obj)
            return (_ARR, len(leaves) - 1)
        if isinstance(obj, dict):
            return {k: walk(v) for k, v in obj.items()}
        if isinstance(obj, list):
            return [walk(v) for v in obj]
        return obj

    template = walk(tables) if tables is not None else None

    def rebuild(arrs):
        def un(obj):
            if isinstance(obj, tuple) and len(obj) == 2 and obj[0] == _ARR:
                return arrs[obj[1]]
            if isinstance(obj, dict):
                return {k: un(v) for k, v in obj.items()}
            if isinstance(obj, list):
                return [un(v) for v in obj]
            return obj

        return un(template)

    return leaves, rebuild


# =========================================================================
# step builders
# =========================================================================
class ShardedServe:
    """Jitted sharded prefill/decode for one (cfg, mesh, tables) config.

    ``mode="gspmd"``: plain ``jax.jit`` — committed inputs plus the
    model's sharding constraints (under :func:`exact_tp`) drive GSPMD.
    ``mode="shard_map"``: a top-level shard_map manual over every mesh
    axis — each shard runs the full per-device program (batch split over
    dp, experts split over the model axis), table arrays ride in as
    explicit replicated operands, and the layer stacks keep ``lax.scan``
    (fully-manual regions never python-unroll; see
    ``repro.nn.sharding.layer_scan``).  Manual mode replicates all table
    slabs — a layer-sharded stack is only addressable with GSPMD
    gather-at-use.
    """

    def __init__(self, cfg: ArchConfig, mesh, lut_tables: dict | None = None,
                 *, mode: str = "gspmd",
                 policy: PlacementPolicy | None = None,
                 kv_dtype: str = "bfloat16"):
        if mode not in ("gspmd", "shard_map"):
            raise ValueError(
                f"ShardedServe: unknown mode {mode!r} "
                f"(expected 'gspmd' or 'shard_map')")
        self.cfg = cfg
        self.mesh = mesh
        self.mode = mode
        self.kv_dtype = kv_dtype
        if mode == "shard_map":
            policy = PlacementPolicy(shard_threshold_bytes=1 << 62)
        self.tables, self.placement = place_tables(lut_tables, mesh, policy)
        self._dp = tuple(a for a in DP_AXES if a in mesh.axis_names) or None
        if mode == "gspmd":
            self._build_gspmd()
        else:
            self._build_manual()

    # -- placement helpers -------------------------------------------------
    def place_params(self, params):
        return jax.device_put(params,
                              serve_param_shardings(self.cfg, self.mesh))

    def place_batch(self, batch: dict) -> dict:
        return batch_placement(self.mesh, batch)

    def place_cache(self, cache):
        return jax.device_put(
            cache,
            jax.tree_util.tree_map_with_path(
                lambda path, leaf: named_sharding(
                    self.mesh, *_state_axes(path, leaf), shape=leaf.shape),
                cache))

    # -- gspmd mode --------------------------------------------------------
    def _gather_weights(self, params):
        """Entry-of-step weight gather: re-constrain every non-expert
        param to replicated so downstream float math runs at exactly the
        single-device shapes (all-gather is bitwise-lossless; sharded
        compute is not — see :func:`serve_param_shardings`).  Expert
        stacks keep their expert-dim sharding: each expert's GEMM shape
        is identical sharded or not, and the combine adds disjoint
        contributions in expert order."""
        from jax.sharding import PartitionSpec as P

        rep = jax.sharding.NamedSharding(self.mesh, P())

        def fix(path, w):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name in _EXPERT_PARAMS:
                return w
            return jax.lax.with_sharding_constraint(w, rep)

        return jax.tree_util.tree_map_with_path(fix, params)

    def _build_gspmd(self):
        from .decode import decode_step, prefill, prefill_replay

        cfg, mesh = self.cfg, self.mesh
        # The table slabs ride in as explicit jitted operands, not
        # closures: jit lowers a closed-over array as a baked constant
        # read through one logical value, which both discards the policy
        # placement (a layer-sharded stack would re-materialize
        # replicated) and hides per-device buffer divergence (the mesh
        # suite's mis-replication control must be able to see it).
        tab_leaves, rebuild = split_table_operands(self.tables)
        self._tab_leaves = tab_leaves

        def _prefill(params, batch, max_seq, tabs):
            with use_mesh(mesh), exact_tp():
                params = self._gather_weights(params)
                return prefill(params, cfg, batch, max_seq=max_seq,
                               lut_tables=rebuild(tabs))

        def _step(params, cache, tok, pos, tabs):
            with use_mesh(mesh), exact_tp():
                params = self._gather_weights(params)
                return decode_step(params, cfg, cache, tok, pos,
                                   lut_tables=rebuild(tabs))

        def _replay(params, cache, tokens, start_pos, tabs):
            with use_mesh(mesh), exact_tp():
                params = self._gather_weights(params)
                return prefill_replay(params, cfg, cache, tokens, start_pos,
                                      lut_tables=rebuild(tabs))

        self._prefill = jax.jit(_prefill, static_argnums=(2,))
        self._step = jax.jit(_step)
        self._replay = jax.jit(_replay, static_argnums=(3,))

    # -- manual (fully-manual shard_map) mode ------------------------------
    def _pspec_of(self, tree, assign):
        return jax.tree_util.tree_map_with_path(assign, tree)

    def _param_pspecs(self, params):
        n_tp = int(self.mesh.shape.get(TP_AXIS, 1))

        def assign(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if (name in _EXPERT_PARAMS and n_tp > 1
                    and leaf.shape[1] % n_tp == 0):
                return P(*((None, TP_AXIS) + (None,) * (leaf.ndim - 2)))
            return P()

        return self._pspec_of(params, assign)

    def _state_pspecs(self, state):
        dp = self._dp

        def assign(path, leaf):
            axes = _state_axes(path, leaf)
            return P(*(dp if a == "dp" else None for a in axes))

        return self._pspec_of(state, assign)

    def _build_manual(self):
        from .decode import decode_step, prefill

        cfg, mesh = self.cfg, self.mesh
        axes = tuple(mesh.axis_names)
        dp = self._dp
        tab_leaves, rebuild = split_table_operands(self.tables)
        tab_specs = [P()] * len(tab_leaves)
        self._tab_leaves = tab_leaves

        def _step(params, cache, tok, pos, tabs):
            def inner(params, cache, tok, pos, tabs):
                with use_mesh(mesh), manual_axes(axes):
                    tables = rebuild(tabs) if self.tables else None
                    return decode_step(params, cfg, cache, tok, pos,
                                       lut_tables=tables)

            return shard_map(
                inner, mesh=mesh,
                in_specs=(self._param_pspecs(params),
                          self._state_pspecs(cache), P(dp, None), P(),
                          tab_specs),
                out_specs=(P(dp, None, None), self._state_pspecs(cache)),
                check_vma=False,
            )(params, cache, tok, pos, tabs)

        def _prefill(params, batch, max_seq, tabs):
            out_state = jax.eval_shape(
                lambda p, b: prefill(p, cfg, b, max_seq=max_seq,
                                     lut_tables=self.tables),
                params, batch)[1]

            def inner(params, batch, tabs):
                with use_mesh(mesh), manual_axes(axes):
                    tables = rebuild(tabs) if self.tables else None
                    return prefill(params, cfg, batch, max_seq=max_seq,
                                   lut_tables=tables)

            bspec = {k: P(dp, *(None,) * (v.ndim - 1))
                     for k, v in batch.items()}
            return shard_map(
                inner, mesh=mesh,
                in_specs=(self._param_pspecs(params), bspec, tab_specs),
                out_specs=(P(dp, None, None), self._state_pspecs(out_state)),
                check_vma=False,
            )(params, batch, tabs)

        self._manual_step = _step
        self._manual_prefill = jax.jit(_prefill, static_argnums=(2,))
        self._jit_step = jax.jit(_step)

    # -- public API --------------------------------------------------------
    def decode(self, params, cache, tok, pos):
        if self.mode == "gspmd":
            return self._step(params, cache, tok, pos, self._tab_leaves)
        return self._jit_step(params, cache, tok, jnp.asarray(pos),
                              self._tab_leaves)

    def replay(self, params, cache, tokens, start_pos: int = 0):
        if self.mode != "gspmd":
            raise NotImplementedError(
                "prefill replay is served in gspmd mode only")
        return self._replay(params, cache, tokens, start_pos,
                            self._tab_leaves)

    @property
    def table_operands(self) -> list:
        """The placed table slabs, passed as the last argument of every
        program this object lowers (``lower_prefill`` / ``lower_decode``:
        call the compiled program as ``exe(*args, serve.table_operands)``
        without the static ``max_seq``)."""
        return self._tab_leaves

    def lower_prefill(self, params, batch: dict, max_seq: int):
        """Lower (no compile) the prefill program for ``max_seq``."""
        fn = self._prefill if self.mode == "gspmd" else self._manual_prefill
        return fn.lower(params, batch, max_seq, self._tab_leaves)

    def lower_decode(self, params, cache, tok, pos):
        """Lower (no compile) one decode step — the mesh suite's HLO /
        compile-count checks."""
        if self.mode == "gspmd":
            return self._step.lower(params, cache, tok, jnp.asarray(pos),
                                    self._tab_leaves)
        return self._jit_step.lower(params, cache, tok, jnp.asarray(pos),
                                    self._tab_leaves)
