"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs_per_device / PEAK_FLOPS
    memory     = HLO_bytes_per_device / HBM_BW
    collective = collective_operand_bytes_per_device / ICI_BW

``cost_analysis()`` supplies per-device FLOPs and bytes; collective bytes
are parsed from the post-SPMD HLO text (sum of operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute).
MODEL_FLOPS (6*N*D train / 2*N*D inference, N = active params) gives the
useful-compute ratio that catches remat/dispatch waste.
"""

from __future__ import annotations

import re

import numpy as np

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# instruction definition: "  %name = <result types> <opcode>(...)" — the
# result types may be a tuple "(f32[..], s32[..])"
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([^=]*?)\s+"
                     r"([\w\-]+)\(")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(([^)]*)\)")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _types_bytes(type_str: str) -> int:
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(type_str))


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum *operand* bytes per collective kind from (post-SPMD) HLO text.

    Modern HLO printing references operands by name without inline types,
    so a first pass builds a name -> result-type symbol table; collective
    operand names resolve against it (fallback: the collective's own
    result type — exact for all-reduce, upper bound for all-gather).
    """
    shapes: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        result_types, kind, operand_str = m.groups()
        total = 0
        for op in operand_str.split(","):
            op = op.strip().lstrip("%")
            if _SHAPE_RE.search(op):          # inline-typed operand
                total += _types_bytes(op)
            elif op in shapes:
                total += _types_bytes(shapes[op])
        if total == 0:                        # fallback: result type
            total = _types_bytes(result_types)
        out[kind] += total
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def collective_breakdown(hlo_text: str, top: int = 8):
    """(kind, operand-shape, count, total-bytes) for the largest collective
    op groups — the §Perf diagnosis view."""
    shapes: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    groups: dict[tuple, list] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        result_types, kind, operand_str = m.groups()
        ops = []
        total = 0
        for op in operand_str.split(","):
            op = op.strip().lstrip("%")
            t = op if _SHAPE_RE.search(op) else shapes.get(op, "")
            ops.append(t.strip())
            total += _types_bytes(t)
        if total == 0:
            total = _types_bytes(result_types)
            ops = [result_types.strip()]
        key = (kind, ops[0])
        rec = groups.setdefault(key, [0, 0])
        rec[0] += 1
        rec[1] += total
    out = sorted(((k[0], k[1], c, b) for (k, (c, b)) in groups.items()),
                 key=lambda t: -t[3])
    return out[:top]


def normalize_cost(cost) -> dict:
    """``compiled.cost_analysis()`` (a dict, or None) -> flat dict."""
    return dict(cost or {})


def roofline_terms(cost: dict, coll_bytes: int) -> dict[str, float]:
    cost = normalize_cost(cost)
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = coll_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    bound = max(compute_s, memory_s, collective_s)
    terms.update({
        "dominant": dom,
        "step_lower_bound_s": bound,
        "roofline_fraction": compute_s / bound if bound > 0 else 0.0,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": float(coll_bytes),
    })
    return terms


# -- MODEL_FLOPS (useful compute) ------------------------------------------

def lm_model_flops(arch, shape_name: str) -> float:
    """6*N_active*D for train, 2*N_active*D for inference (global)."""
    cfg = arch.cfg
    spec = arch.shapes[shape_name]
    n_active = cfg.active_param_count()
    b, s = spec["global_batch"], spec["seq_len"]
    if spec["kind"] == "train":
        tokens = 2 * b * s                     # query + passage towers
        return 6.0 * n_active * tokens
    if spec["kind"] == "encode":
        return 2.0 * n_active * b * s
    # decode: 1 token/seq; attention reads dominate but count param-flops
    kv_flops = (2.0 * b * s * cfg.n_layers
                * cfg.n_kv_heads * cfg.head_dim * 2)
    return 2.0 * n_active * b + kv_flops


def gnn_model_flops(arch, shape_name: str) -> float:
    spec = arch.shapes[shape_name]
    cfg = arch.shape_cfg(shape_name)
    d0, dh = cfg.d_feat, cfg.d_hidden
    per_node = 2 * (d0 * dh * 2 + dh * dh * 2)       # 2 layers, self+neigh
    if spec["mode"] == "full":
        n = spec["n_nodes"]
        e = spec["n_edges"]
        msgs = 2 * e * (d0 + dh)                      # gather+reduce adds
        return 3.0 * (n * per_node + msgs)            # fwd+bwd
    if spec["mode"] == "minibatch":
        b = spec["batch_nodes"]
        f1, f2 = spec["fanouts"]
        nodes = 2 * b * (1 + f1 + f1 * f2)            # anchor+positive trees
        return 3.0 * nodes * per_node
    g, n = spec["n_graphs"], spec["n_nodes"]
    return 3.0 * 2 * g * n * per_node


def recsys_model_flops(arch, shape_name: str) -> float:
    spec = arch.shapes[shape_name]
    cfg = arch.cfg
    d = cfg.embed_dim
    f = cfg.n_fields
    mlp_in = {"deepfm": f * d, "wide_deep": f * d,
              "autoint": f * cfg.n_heads * cfg.d_attn,
              "bst": (cfg.seq_len + 1 + cfg.n_profile_fields) * d}[cfg.kind]
    dims = (mlp_in,) + tuple(cfg.mlp_dims) + (1,)
    mlp = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    inter = 0
    if cfg.kind == "autoint":
        d_in = d
        for _ in range(cfg.n_attn_layers):
            dh = cfg.n_heads * cfg.d_attn
            inter += 2 * f * d_in * dh * 4 + 2 * f * f * dh * 2
            d_in = dh
        mlp = 2 * f * d_in * 1
    if cfg.kind == "bst":
        s = cfg.seq_len + 1
        inter = 2 * s * d * d * 4 + 2 * s * s * d * 2 + \
            2 * s * d * cfg.bst_d_ff * 2
    if cfg.kind == "deepfm":
        inter = 2 * f * d * 2
    per_ex = mlp + inter + f * d                      # + embedding reads
    b = (spec["n_candidates"] if spec["kind"] == "retrieval"
         else spec["batch"])
    mult = 3.0 if spec["kind"] == "train" else 1.0
    return mult * per_ex * b


def model_flops(arch, shape_name: str) -> float:
    return {"lm": lm_model_flops, "gnn": gnn_model_flops,
            "recsys": recsys_model_flops}[arch.family](arch, shape_name)


# -- analytic HBM-traffic model ------------------------------------------------
# XLA:CPU cost_analysis "bytes accessed" is fusion-blind (every elementwise
# op counts operand+result traffic), overstating TPU HBM bytes by ~10-30x.
# These closed forms estimate per-device HBM traffic under TPU fusion:
# weights stream once per pass, activations r/w at layer boundaries, the
# attention score matrix r/w unless a flash kernel is used.

def _mesh_dp_tp(mesh_shape: dict) -> tuple[int, int]:
    dp = int(np.prod([mesh_shape.get(a, 1) for a in ("pod", "data")]))
    return dp, mesh_shape.get("model", 1)


def lm_analytic_bytes(arch, shape_name: str, mesh_shape: dict,
                      flash_attn: bool = False) -> float:
    cfg = arch.cfg
    spec = arch.shapes[shape_name]
    dp, tp = _mesh_dp_tp(mesh_shape)
    b, s = spec["global_batch"], spec["seq_len"]
    b_loc = max(1, b // dp)
    bpe = 2
    p_total = cfg.param_count()
    p_shard = p_total / (dp * tp)          # FSDP x TP resident shard

    if spec["kind"] == "serve":
        # decode: read the full resident param shard + the cache shard once
        cache = (cfg.n_layers * b * s * cfg.n_kv_heads * cfg.head_dim
                 * 2 * bpe) / (dp * tp if b == 1 or
                               cfg.n_kv_heads % tp else dp * tp)
        if cfg.moe:
            # only active experts' weights are gathered per token
            active = cfg.active_param_count()
            p_read = (active / tp) * bpe * max(1, b_loc)
        else:
            p_read = p_total / tp * bpe    # weights stream once (all-gathered)
        return p_read + cache

    passes = 3.0 if spec["kind"] == "train" else 1.0
    # weights stream through each device once per pass (FSDP all-gather)
    w_traffic = passes * (p_total / tp) * bpe
    if spec["kind"] == "train":
        w_traffic += p_shard * (4 + 4) * 2      # grads + opt r/w fp32
    # activation boundaries: ~6 r/w of (B,S,d) per layer per pass
    act = passes * cfg.n_layers * 6 * b_loc * s * cfg.d_model * bpe / (
        tp if cfg.seq_shard_acts else 1)
    # attention scores: r/w of (B,*,Sq,Skv) fp32 per layer unless flash
    scores = 0.0
    if not flash_attn and s > 1:
        if cfg.seq_shard_attn:
            rows = s // tp
            heads = cfg.n_kv_heads * (cfg.n_heads // cfg.n_kv_heads)
        else:
            hs = tp if cfg.n_kv_heads % tp == 0 else 1
            rows = s
            heads = (cfg.n_kv_heads // hs) * (cfg.n_heads // cfg.n_kv_heads)
        scores = passes * cfg.n_layers * 4 * b_loc * heads * rows * s * 4
    # MoE expert weights: all local experts stream per pass
    moe = 0.0
    if cfg.moe:
        e_shard = tp if cfg.n_experts % tp == 0 else 1
        f_shard = 1 if cfg.n_experts % tp == 0 else (
            tp if cfg.moe_d_ff % tp == 0 else 1)
        moe = passes * cfg.n_moe_layers * (
            cfg.n_experts // e_shard) * 3 * cfg.d_model * (
            cfg.moe_d_ff // f_shard) * bpe / dp   # FSDP share of experts
    return w_traffic + act + scores + moe


def gnn_analytic_bytes(arch, shape_name: str, mesh_shape: dict) -> float:
    spec = arch.shapes[shape_name]
    cfg = arch.shape_cfg(shape_name)
    dp, _ = _mesh_dp_tp(mesh_shape)
    if spec["mode"] == "full":
        n, e = spec["n_nodes"], spec["n_edges"]
        per = (n * (cfg.d_feat + 4 * cfg.d_hidden)
               + 2 * e * (cfg.d_feat + cfg.d_hidden)) * 4
        return 3.0 * per / dp
    if spec["mode"] == "minibatch":
        b = spec["batch_nodes"]
        f1, f2 = spec["fanouts"]
        nodes = 2 * b * (1 + f1 + f1 * f2)
        return 3.0 * 4 * nodes * max(cfg.d_feat, cfg.d_hidden) * 4 / dp
    g, n = spec["n_graphs"], spec["n_nodes"]
    return 3.0 * 4 * 2 * g * n * max(cfg.d_feat, cfg.d_hidden) * 4 / dp


def recsys_analytic_bytes(arch, shape_name: str, mesh_shape: dict) -> float:
    spec = arch.shapes[shape_name]
    cfg = arch.cfg
    dp, tp = _mesh_dp_tp(mesh_shape)
    b = (spec["n_candidates"] if spec["kind"] == "retrieval"
         else spec["batch"])
    b_loc = max(1, b // dp)
    rows = b_loc * cfg.n_fields * cfg.embed_dim * 4       # gathered rows
    mlp_params = sum(a * bb for a, bb in zip(
        ((cfg.n_fields * cfg.embed_dim,) + tuple(cfg.mlp_dims)),
        (tuple(cfg.mlp_dims) + (1,)))) * 4
    act = b_loc * (cfg.n_fields * cfg.embed_dim
                   + sum(cfg.mlp_dims) + 1) * 4 * 2
    passes = 3.0 if spec["kind"] == "train" else 1.0
    table_grad = 0.0
    if spec["kind"] == "train":
        # dense scatter-add gradient + adamw update over the table shard
        table_grad = (cfg.total_vocab // tp) * cfg.embed_dim * 4 * 4
    return passes * (rows + mlp_params + act) + table_grad


def analytic_bytes(arch, shape_name: str, mesh_shape: dict,
                   flash_attn: bool = False) -> float:
    if arch.family == "lm":
        return lm_analytic_bytes(arch, shape_name, mesh_shape, flash_attn)
    if arch.family == "gnn":
        return gnn_analytic_bytes(arch, shape_name, mesh_shape)
    return recsys_analytic_bytes(arch, shape_name, mesh_shape)
