"""Per-cell step builders: (arch x shape x mesh) -> jitted fn + abstract args.

Every builder returns a CellPlan whose ``abstract_args`` are
ShapeDtypeStructs carrying NamedShardings, so ``fn.lower(*abstract_args)``
compiles the full production graph with zero allocation (the dry-run), and
the same plan drives real execution when given concrete arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ArchDef, ShapeCell, get_arch
from repro.core.exchange import ExchangeConfig, PSExchange
from repro.launch import mesh as meshlib
from repro.models.common import Dist
from repro.models.gnn import equiformer_v2 as EQ
from repro.models.gnn.spherical import packed_wigner_size
from repro.models.recsys import models as RS
from repro.models import resnet as RN
from repro.models import transformer as T
from repro.optim.optimizers import OptimizerSpec, adamw, momentum, sgd
from repro.runtime.trainer import make_ps_train_step


@dataclasses.dataclass
class CellPlan:
    arch_id: str
    shape: str
    kind: str
    fn: Any  # jitted callable
    abstract_args: tuple
    meta: dict


def _sds(mesh, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _abstract_tree(mesh, tree_sds, tree_specs):
    def mk(x, s):
        return _sds(mesh, x.shape, x.dtype, s)

    return jax.tree.map(mk, tree_sds, tree_specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def default_optimizer(family: str) -> OptimizerSpec:
    # per-family production defaults: LMs/GNN AdamW; recsys SGD (MLPerf DLRM);
    # vision momentum (the paper's ImageNet setting)
    return {
        "lm": adamw(3e-4, weight_decay=0.1),
        "gnn": adamw(1e-3),
        "recsys": sgd(1e-2),
        "vision": momentum(0.1, 0.9),
    }[family]


def make_exchange(mesh, family: str, strategy: str = "pbox",
                  opt: OptimizerSpec | None = None,
                  exchange_cfg: ExchangeConfig | None = None) -> PSExchange:
    wa = meshlib.worker_axes(mesh)
    pa = meshlib.pod_axis(mesh)
    cfg = exchange_cfg or ExchangeConfig(strategy=strategy)
    if cfg.strategy == "pbox_hier" and pa is None:
        cfg = dataclasses.replace(cfg, strategy="pbox")
    return PSExchange(opt or default_optimizer(family), cfg, wa,
                      pa if cfg.strategy == "pbox_hier" else None)


# ===========================================================================
# LM cells
# ===========================================================================

def _lm_dist(mesh) -> Dist:
    return Dist(model_axis="model", data_axes=meshlib.worker_axes(mesh),
                tp=mesh.shape["model"])


def _training_only(cfg: T.TransformerConfig):
    if cfg.deepseek_block:
        raise ValueError(f"{cfg.name}: the DeepSeek-V3 block (MLA, held "
                         f"experts) has training cells only")


def build_lm_train(arch: ArchDef, cell: ShapeCell, mesh,
                   exchange: PSExchange, smoke: bool = False,
                   variant: str | None = None) -> CellPlan:
    cfg = arch.smoke_config if smoke else arch.config
    tp = mesh.shape["model"]
    if variant == "sp":
        # beyond-paper: sequence-parallel activations (EXPERIMENTS.md §Perf)
        cfg = dataclasses.replace(cfg, seq_parallel=True)
    dist = _lm_dist(mesh)
    wa = meshlib.worker_axes(mesh)
    gb, s = cell.params["global_batch"], cell.params["seq_len"]
    if smoke:
        gb, s = meshlib.num_workers(mesh) * 2, 32
    mb = (arch.microbatches or {}).get(cell.name, 1) if not smoke else 1
    if variant == "sp" and mb > 1:
        mb = max(mb // 4, 1)  # 1/tp activations afford larger microbatches

    specs = T.make_param_specs(cfg, tp)
    tags = T.grad_sync(cfg, tp)
    gshape = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0), tp=tp)
    )

    def loss_fn(params, batch, dist):
        return T.lm_loss(params, batch["tokens"], batch["labels"], cfg, dist, tp)

    batch_spec = {"tokens": P(wa), "labels": P(wa)}
    step, space, sspecs, ng = make_ps_train_step(
        mesh, loss_fn=loss_fn, param_specs=specs, sync_tags=tags,
        global_param_template=gshape, exchange=exchange, dist=dist,
        batch_spec=batch_spec, ps_dtype=cfg.param_dtype, microbatches=mb,
    )
    n_state = exchange.spec.num_state_slots
    args = (
        _sds(mesh, (ng, space.flat_elems), cfg.param_dtype, sspecs["pflat"]),
        tuple(_sds(mesh, (ng, space.flat_elems), jnp.float32, sp)
              for sp in sspecs["slots"]),
        None,
        _sds(mesh, (), jnp.int32, P()),
        {
            "tokens": _sds(mesh, (gb, s), jnp.int32, P(wa)),
            "labels": _sds(mesh, (gb, s), jnp.int32, P(wa)),
        },
    )
    n_act = cfg.active_param_count()
    return CellPlan(arch.arch_id, cell.name, "train", step, args, {
        "space": space, "sspecs": sspecs, "n_groups": ng,
        "model_flops": 6.0 * n_act * gb * s,
        "tokens": gb * s, "params": cfg.param_count(),
        "microbatches": mb,
    })


def build_lm_prefill(arch: ArchDef, cell: ShapeCell, mesh,
                     smoke: bool = False) -> CellPlan:
    cfg = arch.smoke_config if smoke else arch.config
    _training_only(cfg)
    tp = mesh.shape["model"]
    dist = _lm_dist(mesh)
    wa = meshlib.worker_axes(mesh)
    gb, s = cell.params["global_batch"], cell.params["seq_len"]
    if smoke:
        gb, s = meshlib.num_workers(mesh), 32
    specs = T.make_param_specs(cfg, tp)
    gshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0), tp=tp))
    pargs = _abstract_tree(mesh, gshape, specs)

    def fn(params, tokens):
        return T.prefill(params, tokens, cfg, dist, tp, s)

    cache_spec = {"k": P(None, wa, "model"), "v": P(None, wa, "model")}
    shmap = jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(wa)),
        out_specs=(P(wa), cache_spec), check_vma=False)
    n_act = cfg.active_param_count()
    attn_flops = (
        4.0 * gb * cfg.n_layers * cfg.n_heads * cfg.head_dim * s * s / 2
    )
    return CellPlan(arch.arch_id, cell.name, "prefill", jax.jit(shmap), (
        pargs, _sds(mesh, (gb, s), jnp.int32, P(wa))),
        {"model_flops": 2.0 * n_act * gb * s + attn_flops, "tokens": gb * s})


def build_lm_decode(arch: ArchDef, cell: ShapeCell, mesh,
                    smoke: bool = False) -> CellPlan:
    cfg = arch.smoke_config if smoke else arch.config
    _training_only(cfg)
    tp = mesh.shape["model"]
    dist = _lm_dist(mesh)
    wa = meshlib.worker_axes(mesh)
    gb, s = cell.params["global_batch"], cell.params["seq_len"]
    if smoke:
        gb, s = meshlib.num_workers(mesh), 64
    nw = meshlib.num_workers(mesh)
    b_loc = gb // nw if gb >= nw else gb
    specs = T.make_param_specs(cfg, tp)
    gshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0), tp=tp))
    pargs = _abstract_tree(mesh, gshape, specs)
    batch_rep = gb < nw  # B=1 long-context: replicate over workers
    bspec = P(None) if batch_rep else P(wa)

    def fn(params, token, cache, pos):
        return T.decode_step(params, token, cache, pos, cfg, dist, tp)

    cache_spec = {"k": P(None, None if batch_rep else wa, "model"),
                  "v": P(None, None if batch_rep else wa, "model")}
    shmap = jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, bspec, cache_spec, P()),
        out_specs=(bspec, cache_spec), check_vma=False)
    cache_shape = (cfg.n_layers, gb, s, cfg.n_kv_heads, cfg.head_dim)
    cache = {
        "k": _sds(mesh, cache_shape, cfg.dtype, cache_spec["k"]),
        "v": _sds(mesh, cache_shape, cfg.dtype, cache_spec["v"]),
    }
    n_act = cfg.active_param_count()
    kv_flops = 4.0 * gb * cfg.n_layers * cfg.n_heads * cfg.head_dim * s
    return CellPlan(arch.arch_id, cell.name, "decode", jax.jit(shmap), (
        pargs, _sds(mesh, (gb,), jnp.int32, bspec), cache,
        _sds(mesh, (), jnp.int32, P())),
        {"model_flops": 2.0 * n_act * gb + kv_flops, "tokens": gb})


def build_lm_decode_long(arch: ArchDef, cell: ShapeCell, mesh,
                         smoke: bool = False) -> CellPlan:
    """Unrolled decode with per-layer cache sizes (sliding-window archs)."""
    cfg = arch.smoke_config if smoke else arch.config
    _training_only(cfg)
    tp = mesh.shape["model"]
    dist = _lm_dist(mesh)
    gb, s = cell.params["global_batch"], cell.params["seq_len"]
    if smoke:
        gb, s = 1, 64
    specs = T.make_param_specs(cfg, tp)
    gshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0), tp=tp))
    pargs = _abstract_tree(mesh, gshape, specs)

    def fn(params, token, caches, pos):
        return T.decode_step_unrolled(params, token, caches, pos, cfg, dist, tp)

    cache_specs, cache_args = [], []
    for li in range(cfg.n_layers):
        glob = cfg.sliding_window is None or (
            cfg.global_every > 0 and (li + 1) % cfg.global_every == 0)
        if glob:
            sp = {"k": P(None, "model"), "v": P(None, "model")}
            shape = (gb, s, cfg.n_kv_heads, cfg.head_dim)
        else:
            sp = {"k": P(), "v": P()}
            w = min(cfg.sliding_window, s)
            shape = (gb, w, cfg.n_kv_heads, cfg.head_dim)
        cache_specs.append(sp)
        cache_args.append({"k": _sds(mesh, shape, cfg.dtype, sp["k"]),
                           "v": _sds(mesh, shape, cfg.dtype, sp["v"])})
    shmap = jax.shard_map(
        fn, mesh=mesh, in_specs=(specs, P(None), cache_specs, P()),
        out_specs=(P(None), cache_specs), check_vma=False)
    n_act = cfg.active_param_count()
    n_glob = sum(1 for li in range(cfg.n_layers)
                 if cfg.global_every > 0 and (li + 1) % cfg.global_every == 0)
    kv_flops = 4.0 * gb * cfg.n_heads * cfg.head_dim * (
        n_glob * s + (cfg.n_layers - n_glob) * (cfg.sliding_window or s))
    return CellPlan(arch.arch_id, cell.name, "decode_long", jax.jit(shmap), (
        pargs, _sds(mesh, (gb,), jnp.int32, P(None)), cache_args,
        _sds(mesh, (), jnp.int32, P())),
        {"model_flops": 2.0 * n_act * gb + kv_flops, "tokens": gb})


# ===========================================================================
# recsys cells
# ===========================================================================

_RS_FNS = {
    "dlrm-mlperf": (RS.dlrm_init, RS.dlrm_specs, RS.dlrm_grad_sync,
                    RS.dlrm_loss, RS.dlrm_score, RS.dlrm_user_tower,
                    RS.DLRMConfig),
    "autoint": (RS.autoint_init, RS.autoint_specs, RS.autoint_grad_sync,
                RS.autoint_loss, RS.autoint_score, RS.autoint_user_tower,
                RS.AutoIntConfig),
    "dien": (RS.dien_init, RS.dien_specs, RS.dien_grad_sync, RS.dien_loss,
             RS.dien_score, RS.dien_user_tower, RS.DIENConfig),
    "xdeepfm": (RS.xdeepfm_init, RS.xdeepfm_specs, RS.xdeepfm_grad_sync,
                RS.xdeepfm_loss, RS.xdeepfm_score, RS.xdeepfm_user_tower,
                RS.XDeepFMConfig),
}


def _rs_batch_template(arch_id, cfg, gb, mesh, wa, retrieval_n=None):
    """(ShapeDtypeStructs, specs) for a recsys batch."""
    tp = mesh.shape["model"]
    if retrieval_n is not None:
        b = tp  # replicated user rows, one per model shard
        spec_b = P(None)
    else:
        b = gb
        spec_b = P(wa)
    batch, specs = {}, {}
    if arch_id == "dlrm-mlperf":
        batch["dense"] = _sds(mesh, (b, cfg.n_dense), jnp.float32, spec_b)
        specs["dense"] = spec_b
    if arch_id == "dien":
        batch["hist_items"] = _sds(mesh, (b, cfg.seq_len), jnp.int32, spec_b)
        batch["hist_cats"] = _sds(mesh, (b, cfg.seq_len), jnp.int32, spec_b)
        specs["hist_items"] = spec_b
        specs["hist_cats"] = spec_b
        nf = 2
    else:
        nf = len(cfg.vocabs)
    batch["sparse"] = _sds(mesh, (b, nf), jnp.int32, spec_b)
    specs["sparse"] = spec_b
    batch["labels"] = _sds(mesh, (b,), jnp.int32, spec_b)
    specs["labels"] = spec_b
    if retrieval_n is not None:
        all_ax = tuple(mesh.axis_names)
        batch["cand_ids"] = _sds(mesh, (retrieval_n,), jnp.int32, P(all_ax))
        specs["cand_ids"] = P(all_ax)
    return batch, specs


def build_recsys_cell(arch: ArchDef, cell: ShapeCell, mesh,
                      exchange: PSExchange | None, smoke: bool = False) -> CellPlan:
    cfg = arch.smoke_config if smoke else arch.config
    init_fn, specs_fn, sync_fn, loss_f, score_f, tower_f, _ = _RS_FNS[arch.arch_id]
    tp = mesh.shape["model"]
    wa = meshlib.worker_axes(mesh)
    dist = Dist(model_axis="model", data_axes=wa, tp=tp)
    specs = specs_fn(cfg, tp)
    gshape = jax.eval_shape(lambda: init_fn(cfg, jax.random.PRNGKey(0), tp))
    nw = meshlib.num_workers(mesh)

    if cell.kind == "train":
        gb = cell.params["batch"] if not smoke else nw * tp * 2
        exchange = exchange or make_exchange(mesh, "recsys")
        batch_t, batch_spec = _rs_batch_template(arch.arch_id, cfg, gb, mesh, wa)
        step, space, sspecs, ng = make_ps_train_step(
            mesh, loss_fn=lambda p, b, d: loss_f(p, b, cfg, d),
            param_specs=specs, sync_tags=sync_fn(cfg, tp),
            global_param_template=gshape, exchange=exchange, dist=dist,
            batch_spec=batch_spec, loss_div_tp=False,  # bce_loss divides already
        )
        args = (
            _sds(mesh, (ng, space.flat_elems), jnp.float32, sspecs["pflat"]),
            tuple(_sds(mesh, (ng, space.flat_elems), jnp.float32, sp)
                  for sp in sspecs["slots"]),
            None, _sds(mesh, (), jnp.int32, P()), batch_t,
        )
        return CellPlan(arch.arch_id, cell.name, "train", step, args, {
            "space": space, "sspecs": sspecs, "n_groups": ng,
            "model_flops": 6.0 * _rs_dense_flops(arch.arch_id, cfg) * gb,
            "examples": gb})

    if cell.kind == "serve":
        gb = cell.params["batch"] if not smoke else nw * tp * 2
        batch_t, batch_spec = _rs_batch_template(arch.arch_id, cfg, gb, mesh, wa)
        batch_t.pop("labels"), batch_spec.pop("labels")
        out_spec = P(wa + ("model",))

        def fn(params, batch):
            return score_f(params, batch, cfg, dist)

        shmap = jax.shard_map(fn, mesh=mesh, in_specs=(specs, batch_spec),
                                  out_specs=out_spec, check_vma=False)
        pargs = _abstract_tree(mesh, gshape, specs)
        return CellPlan(arch.arch_id, cell.name, "serve", jax.jit(shmap),
                        (pargs, batch_t),
                        {"model_flops": 2.0 * _rs_dense_flops(arch.arch_id, cfg) * gb,
                         "examples": gb})

    if cell.kind == "retrieval":
        n = cell.params["n_candidates"] if not smoke else nw * tp * 8
        batch_t, batch_spec = _rs_batch_template(
            arch.arch_id, cfg, 1, mesh, wa, retrieval_n=n)
        batch_t.pop("labels"), batch_spec.pop("labels")
        all_ax = tuple(mesh.axis_names)

        def fn(params, batch):
            return RS.bulk_retrieval(params, batch, tower_f, "t0",
                                     cfg.embed_dim, cfg, dist)

        shmap = jax.shard_map(fn, mesh=mesh, in_specs=(specs, batch_spec),
                                  out_specs=P(all_ax), check_vma=False)
        pargs = _abstract_tree(mesh, gshape, specs)
        return CellPlan(arch.arch_id, cell.name, "retrieval", jax.jit(shmap),
                        (pargs, batch_t),
                        {"model_flops": 2.0 * n * cfg.embed_dim, "examples": n})
    raise ValueError(cell.kind)


def _rs_dense_flops(arch_id: str, cfg) -> float:
    """Per-example dense-stage MAC count (embedding lookups are bytes, not
    flops)."""
    if arch_id == "dlrm-mlperf":
        dims_b = (cfg.n_dense,) + cfg.bot_mlp
        dims_t = (cfg.top_in,) + cfg.top_mlp
        f = sum(a * b for a, b in zip(dims_b, dims_b[1:]))
        f += sum(a * b for a, b in zip(dims_t, dims_t[1:]))
        f += (cfg.n_sparse + 1) ** 2 * cfg.embed_dim / 2
        return f
    if arch_id == "autoint":
        d_in, f = cfg.embed_dim, 0
        for _ in range(cfg.n_attn_layers):
            f += cfg.n_sparse * (4 * d_in * cfg.d_attn
                                 + 2 * cfg.n_sparse * cfg.d_attn)
            d_in = cfg.d_attn
        return f
    if arch_id == "dien":
        g = 3 * (cfg.in_dim + cfg.gru_dim) * cfg.gru_dim
        f = 2 * cfg.seq_len * g  # GRU + AUGRU
        dims = (cfg.mlp_in,) + cfg.mlp
        return f + sum(a * b for a, b in zip(dims, dims[1:]))
    if arch_id == "xdeepfm":
        f, h_prev = 0, cfg.n_sparse
        for h in cfg.cin_layers:
            f += h * h_prev * cfg.n_sparse * cfg.embed_dim
            h_prev = h
        dims = (cfg.n_sparse * cfg.embed_dim,) + cfg.mlp
        return f + sum(a * b for a, b in zip(dims, dims[1:]))
    raise ValueError(arch_id)


def build_recsys_train_sparse(arch: ArchDef, cell: ShapeCell, mesh,
                              smoke: bool = False) -> CellPlan:
    """Beyond-paper optimized recsys training: dense params through the
    chunked PBox exchange, embedding tables via the sparse key-value push
    (runtime/sparse_push.py).  Currently wired for dlrm-mlperf (the
    hillclimbed cell); see EXPERIMENTS.md §Perf."""
    from repro.runtime.sparse_push import make_sparse_recsys_train_step

    if arch.arch_id != "dlrm-mlperf":
        raise NotImplementedError("sparse push is wired for dlrm-mlperf")
    cfg = arch.smoke_config if smoke else arch.config
    tp = mesh.shape["model"]
    wa = meshlib.worker_axes(mesh)
    nw = meshlib.num_workers(mesh)
    dist = Dist(model_axis="model", data_axes=wa, tp=tp)
    gb = cell.params["batch"] if not smoke else nw * tp * 2
    exchange = make_exchange(mesh, "recsys", "pbox")

    full_specs = RS.dlrm_specs(cfg, tp)
    table_specs_ = full_specs["tables"]
    dense_specs = {k: v for k, v in full_specs.items() if k != "tables"}
    full_sync = RS.dlrm_grad_sync(cfg, tp)
    dense_sync = {k: v for k, v in full_sync.items() if k != "tables"}
    gshape = jax.eval_shape(lambda: RS.dlrm_init(cfg, jax.random.PRNGKey(0), tp))
    dense_template = {k: v for k, v in gshape.items() if k != "tables"}
    batch_t, batch_spec = _rs_batch_template(arch.arch_id, cfg, gb, mesh, wa)

    step, space, sspecs = make_sparse_recsys_train_step(
        mesh,
        lookup_fn=lambda tables, b, d: RS.dlrm_lookup(tables, b, d),
        loss_from_emb=lambda dp, e, b, d: RS.dlrm_loss_from_emb(dp, e, b, cfg, d),
        dense_specs=dense_specs, dense_sync=dense_sync,
        dense_template=dense_template, table_specs=table_specs_,
        exchange=exchange, dist=dist, batch_spec=batch_spec,
        table_lr=exchange.spec.lr,
    )
    tables_abs = _abstract_tree(mesh, gshape["tables"], table_specs_)
    n_state = exchange.spec.num_state_slots
    args = (
        _sds(mesh, (tp, space.flat_elems), jnp.float32, sspecs["pflat"]),
        tuple(_sds(mesh, (tp, space.flat_elems), jnp.float32, sp)
              for sp in sspecs["slots"]),
        None, _sds(mesh, (), jnp.int32, P()), tables_abs, batch_t,
    )
    return CellPlan(arch.arch_id, cell.name, "train", step, args, {
        "space": space, "sspecs": sspecs, "n_groups": tp,
        "model_flops": 6.0 * _rs_dense_flops(arch.arch_id, cfg) * gb,
        "examples": gb, "variant": "sparse_push"})


# ===========================================================================
# GNN cells
# ===========================================================================

def _gnn_graph_template(mesh, cell: ShapeCell, cfg: EQ.EquiformerConfig,
                        wa, smoke: bool):
    """(graph SDS dict, specs, effective cfg) for each graph regime."""
    import dataclasses as dc

    nw = meshlib.num_workers(mesh)
    pw = packed_wigner_size(cfg.l_max)
    kind = cell.kind
    p = cell.params

    def node_edge(n, e, d_in, spec):
        g = {
            "node_feat": ((n, d_in), jnp.float32),
            "edge_src": ((e,), jnp.int32),
            "edge_dst": ((e,), jnp.int32),
            "edge_mask": ((e,), jnp.float32),
            "node_mask": ((n,), jnp.float32),
            "wigner": ((e, pw), jnp.float32),
            "rbf": ((e, cfg.n_rbf), jnp.float32),
        }
        sds = {k: _sds(mesh, s, dt, P() if spec is None else spec)
               for k, (s, dt) in g.items()}
        specs = {k: (P() if spec is None else spec) for k in g}
        return sds, specs

    if kind == "graph_full":
        n, e = (p["n_nodes"], p["n_edges"]) if not smoke else (64, 256)
        cfg = dc.replace(cfg, d_in=p["d_feat"] if not smoke else cfg.d_in,
                         n_out=p["n_classes"] if not smoke else cfg.n_out)
        sds, specs = node_edge(n, e, cfg.d_in, None)  # replicated full graph
        sds["labels"] = _sds(mesh, (n,), jnp.int32, P())
        specs["labels"] = P()
        return sds, specs, cfg, False
    if kind == "graph_minibatch":
        pn = p["pad_nodes"] if not smoke else 64
        pe = p["pad_edges"] if not smoke else 256
        cfg = dc.replace(cfg, d_in=p["d_feat"] if not smoke else cfg.d_in,
                         n_out=p["n_classes"] if not smoke else cfg.n_out)
        sds, specs = node_edge(nw * pn, nw * pe, cfg.d_in, P(wa))
        sds["labels"] = _sds(mesh, (nw * pn,), jnp.int32, P(wa))
        specs["labels"] = P(wa)
        return sds, specs, cfg, False
    if kind == "graph_full_large":
        n = p["n_nodes"] if not smoke else 64 * nw
        e = p["n_edges"] if not smoke else 256 * nw
        n = -(-n // nw) * nw
        e = -(-e // nw) * nw
        cfg = dc.replace(cfg, d_in=p["d_feat"] if not smoke else cfg.d_in,
                         n_out=p["n_classes"] if not smoke else cfg.n_out,
                         dtype=jnp.bfloat16)
        sds, specs = node_edge(n, e, cfg.d_in, P(wa))
        sds["labels"] = _sds(mesh, (n,), jnp.int32, P(wa))
        specs["labels"] = P(wa)
        return sds, specs, cfg, True  # dist_nodes
    if kind == "graph_molecule":
        b = p["batch"] if not smoke else nw * 2
        npg, epg = (p["n_nodes"], p["n_edges"]) if not smoke else (8, 16)
        b_w = b // nw if b >= nw else b
        cfg = dc.replace(cfg, d_in=p["n_species"] if not smoke else cfg.d_in,
                         n_out=1, task="graph_reg")
        n, e = b * npg, b * epg
        sds, specs = node_edge(n, e, cfg.d_in, P(wa))
        sds["graph_ids"] = _sds(mesh, (n,), jnp.int32, P(wa))
        specs["graph_ids"] = P(wa)
        sds["targets"] = _sds(mesh, (b,), jnp.float32, P(wa))
        specs["targets"] = P(wa)
        sds["graph_mask"] = _sds(mesh, (b,), jnp.float32, P(wa))
        specs["graph_mask"] = P(wa)
        return sds, specs, cfg, False
    raise ValueError(kind)


def build_gnn_cell(arch: ArchDef, cell: ShapeCell, mesh,
                   exchange: PSExchange | None, smoke: bool = False,
                   variant: str | None = None) -> CellPlan:
    base = arch.smoke_config if smoke else arch.config
    if variant == "ep":
        # beyond-paper: edge-parallel model axis (EXPERIMENTS.md §Perf)
        base = dataclasses.replace(base, edge_parallel=True)
    tp = mesh.shape["model"]
    wa = meshlib.worker_axes(mesh)
    dist = Dist(model_axis="model", data_axes=wa, tp=tp)
    sds, bspecs, cfg, dist_nodes = _gnn_graph_template(mesh, cell, base, wa, smoke)
    if cfg.edge_parallel and tp > 1:
        # edge arrays shard over (workers x model); node arrays over workers
        ea = wa + ("model",)
        nw = meshlib.num_workers(mesh)
        for k in ("edge_src", "edge_dst", "edge_mask", "wigner", "rbf"):
            sp = P(ea) if bspecs[k] != P() else P("model")
            div = nw * tp if sp == P(ea) else tp
            shape = list(sds[k].shape)
            shape[0] = -(-shape[0] // div) * div  # pad edges to shard evenly
            bspecs[k] = sp
            sds[k] = _sds(mesh, tuple(shape), sds[k].dtype, sp)
    specs = EQ.make_param_specs(cfg, tp)
    tags = EQ.grad_sync(cfg, tp)
    gshape = jax.eval_shape(lambda: EQ.init_params(cfg, jax.random.PRNGKey(0), tp))
    exchange = exchange or make_exchange(mesh, "gnn")

    step, space, sspecs, ng = make_ps_train_step(
        mesh,
        loss_fn=lambda p, b, d: EQ.loss_fn(p, b, cfg, d, dist_nodes),
        param_specs=specs, sync_tags=tags, global_param_template=gshape,
        exchange=exchange, dist=dist, batch_spec=bspecs,
        loss_div_tp=False,  # EQ.loss_fn divides by tp itself
    )
    args = (
        _sds(mesh, (ng, space.flat_elems), jnp.float32, sspecs["pflat"]),
        tuple(_sds(mesh, (ng, space.flat_elems), jnp.float32, sp)
              for sp in sspecs["slots"]),
        None, _sds(mesh, (), jnp.int32, P()), sds,
    )
    n_edges = sds["edge_src"].shape[0]
    n_nodes = sds["node_feat"].shape[0]
    return CellPlan(arch.arch_id, cell.name, "train", step, args, {
        "space": space, "sspecs": sspecs, "n_groups": ng,
        "model_flops": _gnn_flops(cfg, n_nodes, n_edges) * 3.0,  # fwd+bwd
        "nodes": n_nodes, "edges": n_edges})


def _gnn_flops(cfg: EQ.EquiformerConfig, n: int, e: int) -> float:
    c, k = cfg.channels, cfg.num_coef
    n0 = cfg.l_max + 1
    so2 = 2.0 * n0 * n0 * c * c  # m=0 block MACs
    for m in range(1, cfg.m_max + 1):
        nl = cfg.l_max + 1 - m
        so2 += 4 * 2.0 * nl * nl * c * c
    rot = 2.0 * sum((2 * l + 1) ** 2 for l in range(cfg.l_max + 1)) * c * 2
    mix = 2.0 * k * c * c * (1 + 2 + 2)  # w_upd + f1 + f2
    return cfg.n_layers * (e * (so2 + rot) + n * mix) * 2.0


# ===========================================================================
# vision (resnet50 — paper workload)
# ===========================================================================

def build_vision_train(arch: ArchDef, cell: ShapeCell, mesh,
                       exchange: PSExchange | None, smoke: bool = False) -> CellPlan:
    if mesh.shape["model"] != 1:
        raise ValueError(f"{arch.arch_id} is pure data parallel: its mesh "
                         f"needs a model axis of 1, got {dict(mesh.shape)}")
    cfg = arch.smoke_config if smoke else arch.config
    wa = meshlib.worker_axes(mesh)
    dist = Dist(model_axis=None, data_axes=wa, tp=1)
    gb = cell.params["global_batch"] if not smoke else mesh.size * 2
    img = cell.params.get("img", 224) if not smoke else 32
    # gradient accumulation holds the published global batch within one
    # chip's HBM; smoke runs keep it so CPU tests take the same scan path
    mb = (arch.microbatches or {}).get(cell.name, 1)
    exchange = exchange or make_exchange(mesh, "vision")
    gshape = jax.eval_shape(lambda: RN.init_params(cfg, jax.random.PRNGKey(0)))
    specs = jax.tree.map(lambda _: P(), gshape,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    tags = jax.tree.map(lambda _: "none", gshape,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    bspec = {"images": P(wa), "labels": P(wa)}
    step, space, sspecs, ng = make_ps_train_step(
        mesh, loss_fn=lambda p, b, d: RN.loss_fn(p, b, cfg, d),
        param_specs=specs, sync_tags=tags, global_param_template=gshape,
        exchange=exchange, dist=dist, batch_spec=bspec, loss_div_tp=False,
        microbatches=mb,
    )
    args = (
        _sds(mesh, (ng, space.flat_elems), jnp.float32, sspecs["pflat"]),
        tuple(_sds(mesh, (ng, space.flat_elems), jnp.float32, sp)
              for sp in sspecs["slots"]),
        None, _sds(mesh, (), jnp.int32, P()),
        {"images": _sds(mesh, (gb, img, img, 3), jnp.float32, P(wa)),
         "labels": _sds(mesh, (gb,), jnp.int32, P(wa))},
    )
    return CellPlan(arch.arch_id, cell.name, "train", step, args, {
        "space": space, "sspecs": sspecs, "n_groups": ng,
        "model_flops": 3 * 2 * 4.1e9 * gb,  # ~4.1 GMACs/img fwd
        "examples": gb, "microbatches": mb})


# ===========================================================================
# dispatch
# ===========================================================================

def build_cell(arch_id: str, shape: str, mesh, *, strategy: str = "pbox",
               exchange_cfg: ExchangeConfig | None = None,
               opt: OptimizerSpec | None = None, smoke: bool = False,
               variant: str | None = None) -> CellPlan:
    arch = get_arch(arch_id)
    cell = arch.cell(shape)
    if cell.skip_reason and not smoke:
        raise ValueError(f"cell skipped: {cell.skip_reason}")
    if arch.family == "lm":
        if cell.kind == "train":
            ex = make_exchange(mesh, "lm", strategy, opt, exchange_cfg)
            return build_lm_train(arch, cell, mesh, ex, smoke, variant)
        if cell.kind == "prefill":
            return build_lm_prefill(arch, cell, mesh, smoke)
        if cell.kind == "decode":
            return build_lm_decode(arch, cell, mesh, smoke)
        if cell.kind == "decode_long":
            return build_lm_decode_long(arch, cell, mesh, smoke)
    if arch.family == "recsys":
        if cell.kind == "train" and strategy == "pbox_sparse":
            return build_recsys_train_sparse(arch, cell, mesh, smoke)
        ex = (make_exchange(mesh, "recsys", strategy, opt, exchange_cfg)
              if cell.kind == "train" else None)
        return build_recsys_cell(arch, cell, mesh, ex, smoke)
    if arch.family == "gnn":
        ex = make_exchange(mesh, "gnn", strategy, opt, exchange_cfg)
        return build_gnn_cell(arch, cell, mesh, ex, smoke, variant)
    if arch.family == "vision":
        ex = make_exchange(mesh, "vision", strategy, opt, exchange_cfg)
        return build_vision_train(arch, cell, mesh, ex, smoke)
    raise ValueError(f"{arch_id}/{shape}")
