"""Cell builder: (arch × shape × mesh) → a lowerable jitted step.

The single glue point between the registry, the sharding rules and the step
functions. Everything is ShapeDtypeStruct-based — building a cell never
allocates a parameter.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import registry as reg
from repro.launch import sharding as shr
from repro.launch.mesh import all_axes, batch_axes
from repro.models import transformer as tfm
from repro.train import steps as steps_mod
from repro.train.optimizer import AdamWConfig

OPT = AdamWConfig()


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape: str
    kind: str
    fn: Callable            # jitted (with in_shardings) — call .lower(*args)
    args: tuple             # ShapeDtypeStruct pytrees
    meta: dict              # model_flops etc. for the roofline
    param_specs: object = None  # PartitionSpec tree for args[0] (IO model)


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _eval_shapes(fn) -> Any:
    return jax.eval_shape(fn)


# ---------------------------------------------------------------------------


def _bf16_serving(params_sds):
    """Serving checkpoints store bf16 weights (§Perf hillclimb B)."""
    def cast(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32 and len(x.shape) >= 2:
            return jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
        return x
    return jax.tree.map(cast, params_sds)


def _lm_cell(spec: reg.ArchSpec, shape: str, mesh) -> Cell:
    cfg = spec.config_for_shape(shape)
    cell = spec.shapes[shape]
    from repro.configs.lm_common import lm_cache_specs

    params_sds = _eval_shapes(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)
    )
    if cell.kind in ("prefill", "decode"):
        params_sds = _bf16_serving(params_sds)
        p_spec = shr.lm_param_specs_inference(params_sds)
    else:
        p_spec = shr.lm_param_specs(params_sds)
    batch_sds = spec.input_specs(cfg, shape)
    b_spec = shr.lm_batch_specs(cell.kind, mesh, batch_sds)
    B, S = cell.sizes["batch"], cell.sizes["seq"]
    ntok_train = B * S

    if cell.kind == "train":
        from repro.train.optimizer import adamw_init
        opt_sds = _eval_shapes(lambda: adamw_init(params_sds))
        o_spec = shr.opt_specs(p_spec)
        fn = jax.jit(
            steps_mod.make_lm_train_step(cfg, OPT),
            in_shardings=(_ns(mesh, p_spec), _ns(mesh, o_spec),
                          _ns(mesh, b_spec)),
            donate_argnums=(0, 1),
        )
        flops = 6 * cfg.n_active_params() * ntok_train
        return Cell(spec.arch_id, shape, cell.kind, fn,
                    (params_sds, opt_sds, batch_sds),
                    {"model_flops": flops, "n_params": cfg.n_params()},
                    param_specs=p_spec)

    if cell.kind == "prefill":
        cache_spec_sh = shr.lm_cache_specs_sharding(cell, mesh)
        cache_out_spec = {
            "kv": [(cache_spec_sh["kv_spec"], cache_spec_sh["kv_spec"])
                   for _ in range(cfg.period)],
            "len": cache_spec_sh["len_spec"],
        }
        logits_spec = P(batch_axes(mesh), shr.TP)
        fn = jax.jit(
            steps_mod.make_lm_prefill_step(cfg, pad_to=S),
            in_shardings=(_ns(mesh, p_spec), _ns(mesh, b_spec)),
            out_shardings=(_ns(mesh, logits_spec), _ns(mesh, cache_out_spec)),
        )
        flops = 2 * cfg.n_active_params() * ntok_train
        return Cell(spec.arch_id, shape, cell.kind, fn,
                    (params_sds, batch_sds),
                    {"model_flops": flops, "n_params": cfg.n_params()},
                    param_specs=p_spec)

    # decode
    cache_sds = lm_cache_specs(cfg, cell)
    csh = shr.lm_cache_specs_sharding(cell, mesh)
    cache_spec = {
        "kv": [(csh["kv_spec"], csh["kv_spec"]) for _ in range(cfg.period)],
        "len": csh["len_spec"],
    }
    logits_spec = P(batch_axes(mesh), shr.TP) if B > 1 else P(None, shr.TP)
    fn = jax.jit(
        steps_mod.make_lm_decode_step(cfg),
        in_shardings=(_ns(mesh, p_spec), _ns(mesh, cache_spec),
                      _ns(mesh, {"tokens": csh["tok_spec"]})),
        out_shardings=(_ns(mesh, logits_spec), _ns(mesh, cache_spec)),
        donate_argnums=(1,),
    )
    # decode flops: one token per sequence + attention against S-cache
    attn_read = (
        cfg.n_layers * 2 * 2 * B * S * cfg.n_kv_heads * cfg.d_head
    )
    flops = 2 * cfg.n_active_params() * B + attn_read
    return Cell(spec.arch_id, shape, cell.kind, fn,
                (params_sds, cache_sds, batch_sds),
                {"model_flops": flops, "n_params": cfg.n_params()},
                param_specs=p_spec)


# ---------------------------------------------------------------------------


def _gnn_cell(spec: reg.ArchSpec, shape: str, mesh) -> Cell:
    cfg = spec.config_for_shape(shape)
    cell = spec.shapes[shape]
    arch = {
        "graphsage-reddit": "graphsage", "gat-cora": "gat",
        "gatedgcn": "gatedgcn", "dimenet": "dimenet",
    }[spec.arch_id]

    def init():
        from repro.models.gnn import dimenet as dmod
        from repro.models.gnn import gat as gmod
        from repro.models.gnn import gatedgcn as ggmod
        from repro.models.gnn import graphsage as smod
        key = jax.random.PRNGKey(0)
        return {
            "graphsage": smod.init_params, "gat": gmod.init_params,
            "gatedgcn": ggmod.init_params, "dimenet": dmod.init_params,
        }[arch](key, cfg)

    params_sds = _eval_shapes(init)
    p_spec = shr.gnn_param_specs(params_sds)
    batch_sds = spec.input_specs(cfg, shape)
    b_spec = shr.gnn_batch_specs(batch_sds, mesh)

    from repro.train.optimizer import adamw_init
    opt_sds = _eval_shapes(lambda: adamw_init(params_sds))
    o_spec = shr.opt_specs(p_spec)
    fn = jax.jit(
        steps_mod.make_gnn_train_step(arch, cfg, OPT),
        in_shardings=(_ns(mesh, p_spec), _ns(mesh, o_spec), _ns(mesh, b_spec)),
        donate_argnums=(0, 1),
    )
    sizes = cell.sizes
    n_param = sum(
        int(jnp.prod(jnp.asarray(x.shape))) for x in jax.tree.leaves(params_sds)
    )
    flops = gnn_model_flops(arch, cfg, sizes, shape)
    return Cell(spec.arch_id, shape, "train", fn,
                (params_sds, opt_sds, batch_sds),
                {"model_flops": int(flops), "n_params": int(n_param)},
                param_specs=p_spec)


def gnn_model_flops(arch: str, cfg, sizes: dict, shape: str) -> float:
    """Analytic fwd+bwd useful FLOPs per family (3× forward convention)."""
    N, E = sizes["n_nodes"], sizes["n_edges"]
    if arch == "graphsage":
        if shape == "minibatch_lg":
            B, (f1, f2) = sizes["batch_nodes"], sizes["fanout"]
            n1, n2 = B * f1, B * f1 * f2
            fwd = 2 * 2 * (n1 * cfg.d_in * cfg.d_hidden
                           + B * cfg.d_hidden * cfg.n_classes)
            fwd += (n2 * cfg.d_in + n1 * cfg.d_hidden)  # masked-mean adds
            return 3 * fwd
        d = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
        fwd = sum(2 * 2 * N * d[i] * d[i + 1] for i in range(cfg.n_layers))
        fwd += cfg.n_layers * E * max(d[:-1])  # segment means
        return 3 * fwd
    if arch == "gat":
        H, dh = cfg.n_heads, cfg.d_hidden
        fwd = 2 * N * cfg.d_in * H * dh + 2 * N * H * dh * cfg.n_classes
        fwd += cfg.n_layers * E * H * (2 * dh + 6)  # scores + softmax + agg
        return 3 * fwd
    if arch == "gatedgcn":
        d = cfg.d_hidden
        per_layer = 2 * (3 * E + 2 * N) * d * d + 8 * E * d
        fwd = (2 * N * cfg.d_in * d + 2 * E * cfg.d_edge_in * d
               + cfg.n_layers * per_layer + 2 * N * d * cfg.n_classes)
        return 3 * fwd
    if arch == "dimenet":
        from repro.configs.gnn_common import max_triplets
        T = max_triplets(shape)
        d, nb = cfg.d_hidden, cfg.n_bilinear
        per_block = (
            2 * T * nb * d * d          # bilinear contraction (dominant)
            + 2 * T * cfg.n_spherical * cfg.n_radial * nb
            + 3 * 2 * E * d * d         # edge MLPs
        )
        fwd = cfg.n_blocks * per_block + 2 * E * (2 * d + cfg.n_radial) * d
        return 3 * fwd
    raise ValueError(arch)


# ---------------------------------------------------------------------------


def _dlrm_cell(spec: reg.ArchSpec, shape: str, mesh) -> Cell:
    from repro.models import dlrm as dlrm_mod
    cfg = spec.config_for_shape(shape)
    cell = spec.shapes[shape]

    params_sds = _eval_shapes(
        lambda: dlrm_mod.init_params(jax.random.PRNGKey(0), cfg)
    )
    p_spec = shr.dlrm_param_specs(params_sds)
    batch_sds = spec.input_specs(cfg, shape)
    b_spec = shr.dlrm_batch_specs(cell.kind, batch_sds, mesh)
    B = cell.sizes["batch"]
    mlp_flops = 2 * B * (
        sum(a * b for a, b in zip((cfg.n_dense,) + cfg.bot_mlp, cfg.bot_mlp))
        + sum(a * b for a, b in zip(
            (cfg.n_interact + cfg.bot_mlp[-1],) + cfg.top_mlp, cfg.top_mlp))
    )

    if cell.kind == "train":
        from repro.train.optimizer import adamw_init
        opt_sds = _eval_shapes(lambda: adamw_init(params_sds))
        o_spec = shr.opt_specs(p_spec)
        fn = jax.jit(
            steps_mod.make_dlrm_train_step(cfg, OPT),
            in_shardings=(_ns(mesh, p_spec), _ns(mesh, o_spec),
                          _ns(mesh, b_spec)),
            donate_argnums=(0, 1),
        )
        return Cell(spec.arch_id, shape, cell.kind, fn,
                    (params_sds, opt_sds, batch_sds),
                    {"model_flops": 3 * mlp_flops}, param_specs=p_spec)
    if cell.kind == "serve":
        fn = jax.jit(
            steps_mod.make_dlrm_serve_step(cfg),
            in_shardings=(_ns(mesh, p_spec), _ns(mesh, b_spec)),
        )
        return Cell(spec.arch_id, shape, cell.kind, fn,
                    (params_sds, batch_sds), {"model_flops": mlp_flops},
                    param_specs=p_spec)
    # retrieval
    M = cell.sizes["n_candidates"]
    fn = jax.jit(
        steps_mod.make_dlrm_retrieval_step(cfg),
        in_shardings=(_ns(mesh, p_spec), _ns(mesh, b_spec)),
    )
    flops = 2 * M * cfg.bot_mlp[-1] + mlp_flops
    return Cell(spec.arch_id, shape, cell.kind, fn,
                (params_sds, batch_sds), {"model_flops": flops},
                param_specs=p_spec)


# ---------------------------------------------------------------------------


def _ipgm_cell(spec: reg.ArchSpec, shape: str, mesh) -> Cell:
    from repro.distributed import ann
    cfg = spec.config_for_shape(shape)
    cell = spec.shapes[shape]
    dp = ann.DistParams(
        index=cfg,
        pod_axis="pod" if "pod" in mesh.axis_names else None,
        vec_dtype="bfloat16",  # §Perf C: halves beam-expansion gather bytes
    )
    state_sds = _eval_shapes(lambda: ann.init_sharded_state(dp, mesh))
    state_spec = jax.tree.map(lambda _: P(dp.axes), state_sds)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    inputs = spec.input_specs(cfg, shape)
    # per-query hop expansion: pool·d_out candidate scorings of dim floats
    sp = cfg.search
    per_q = sp.max_steps * cfg.d_out * cfg.dim * 2
    if cell.kind == "ipgm_query":
        fn = ann.make_query_step(dp, mesh)
        args = (state_sds, inputs["queries"], key_sds)
        flops = cell.sizes["q_batch"] * per_q
    elif cell.kind == "ipgm_delete":
        fn = ann.make_delete_step(dp, mesh, "global")
        args = (state_sds, inputs["gids"], key_sds)
        flops = cell.sizes["batch"] * cfg.eff_d_in * per_q
    else:
        fn = ann.make_insert_step(dp, mesh)
        # round-robin routing: each shard's block holds its share of rows
        n_shards = math.prod(mesh.shape[a] for a in dp.axes)
        batch, dim = inputs["vecs"].shape
        share = -(-batch // n_shards)
        args = (state_sds,
                jax.ShapeDtypeStruct((n_shards, share, dim), jnp.float32),
                jax.ShapeDtypeStruct((n_shards, share), jnp.int32),
                jax.ShapeDtypeStruct((n_shards, share), jnp.bool_), key_sds)
        flops = cell.sizes["batch"] * per_q
    return Cell(spec.arch_id, shape, cell.kind, fn, args,
                {"model_flops": int(flops)}, param_specs=state_spec)


# ---------------------------------------------------------------------------


def build_cell(arch_id: str, shape: str, mesh) -> Cell:
    spec = reg.get_arch(arch_id)
    cell = spec.shapes[shape]
    if cell.skip:
        raise ValueError(f"cell ({arch_id}, {shape}) skipped: {cell.skip}")
    fam = spec.family
    if fam == "lm":
        return _lm_cell(spec, shape, mesh)
    if fam == "gnn":
        return _gnn_cell(spec, shape, mesh)
    if fam == "recsys":
        return _dlrm_cell(spec, shape, mesh)
    if fam == "ipgm":
        return _ipgm_cell(spec, shape, mesh)
    raise ValueError(fam)


def all_cells(include_skipped: bool = False) -> list[tuple[str, str, str | None]]:
    """[(arch, shape, skip_reason)] over the full assignment matrix."""
    out = []
    for arch_id, spec in reg.all_archs().items():
        for shape, cell in spec.shapes.items():
            out.append((arch_id, shape, cell.skip))
    return out
