"""Roofline analysis from the dry-run records (§Roofline deliverable).

Per (arch x shape) single-pod cell:

* compute term    = jaxpr FLOPs / (chips x 197 TF/s bf16)
* memory term     = fusion-adjusted bytes / (chips x 819 GB/s HBM)
* collective term = ring-model link bytes / (chips x 50 GB/s ICI link)

plus MODEL_FLOPS = 6*N*D (6*N_active*D for MoE), the useful-compute ratio
MODEL_FLOPS / step FLOPs, the dominant term and a one-line remedy note.
Sources and caveats (XLA cost_analysis counts loop bodies once; we use
exact jaxpr accounting instead) are documented in the dry-run module.
"""
from __future__ import annotations

import glob
import json
import os

import jax

from repro.configs import SHAPES, get_config
from repro.core.costmodel import tpu_params

# the dry-run cells are compiled for a described TPU v5e
HW = tpu_params("TPU v5 lite")


def model_params(arch: str) -> dict:
    """Total and active (MoE) parameter counts, embeddings excluded from
    the 6ND convention."""
    from repro.models import api
    cfg = get_config(arch)
    abs_params = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg))
    total = sum(int(x.size) for x in jax.tree_util.tree_leaves(abs_params))
    embed = cfg.padded_vocab * cfg.d_model
    if not cfg.tie_embeddings:
        embed *= 2
    non_embed = total - embed
    active = non_embed
    if cfg.moe:
        flat = jax.tree_util.tree_flatten_with_path(abs_params)[0]
        expert = sum(
            int(leaf.size) for path, leaf in flat
            if any(getattr(p, "key", None) in ("gate", "up", "down")
                   and "moe_blocks" in str(path) for p in path)
            and not any(getattr(p, "key", None) == "shared" for p in path)
            and not any(getattr(p, "key", None) == "router" for p in path))
        active = non_embed - expert + expert * cfg.top_k / cfg.n_experts
    return {"total": total, "non_embed": non_embed, "active": int(active)}


def analyze_record(rec: dict, params: dict) -> dict:
    chips = rec["n_devices"]
    flops_dev = rec["flops_per_device"]
    bytes_dev = rec["bytes_per_device"]
    link_dev = rec["collectives"]["total_link_bytes"]
    terms = {
        "compute_s": flops_dev / HW.peak_flops_bf16,
        "memory_s": bytes_dev / HW.hbm_bw,
        "collective_s": link_dev / HW.ici_link_bw,
    }
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    frac = {k: (v / bound if bound else 0.0) for k, v in terms.items()}

    spec = SHAPES[rec["shape"]]
    if rec["kind"] == "train":
        tokens = spec.seq_len * spec.global_batch
        model_flops = 6.0 * params["active"] * tokens
    elif rec["kind"] == "prefill":
        tokens = spec.seq_len * spec.global_batch
        model_flops = 2.0 * params["active"] * tokens
    else:
        tokens = spec.global_batch
        model_flops = 2.0 * params["active"] * tokens
    step_flops = flops_dev * chips
    useful = model_flops / step_flops if step_flops else 0.0
    # roofline fraction: useful model flops vs what the dominant-term time
    # would allow at peak
    ideal_s = model_flops / (chips * HW.peak_flops_bf16)
    achieved = ideal_s / bound if bound else 0.0
    return {
        **{k: float(v) for k, v in terms.items()},
        "dominant": dom.replace("_s", ""),
        "bound_s": float(bound),
        "model_flops": float(model_flops),
        "useful_ratio": float(useful),
        "roofline_fraction": float(achieved),
        "fractions": {k.replace("_s", ""): round(v, 3)
                      for k, v in frac.items()},
    }


_REMEDY = {
    "compute": "reduce recompute (remat policy) / raise MXU utilization "
               "via larger per-chip tiles",
    "memory": "fuse bandwidth-bound chains, cache activations in bf16, "
              "cut optimizer-state traffic (ZeRO offload or lower-"
              "precision statistics)",
    "collective": "reshard to cut boundary collectives (SP<->TP "
                  "handoffs), overlap grad reduce-scatter with backward, "
                  "compress cross-pod gradients",
}


def load_all(dryrun_dir: str = "experiments/dryrun"):
    recs = []
    for f in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        r = json.load(open(f))
        if "error" not in r:
            recs.append(r)
    return recs


def build_table(dryrun_dir: str = "experiments/dryrun",
                mesh: str = "16x16") -> list[dict]:
    rows = []
    pcache: dict[str, dict] = {}
    for rec in load_all(dryrun_dir):
        if rec["mesh"] != mesh:
            continue
        if rec["arch"] not in pcache:
            pcache[rec["arch"]] = model_params(rec["arch"])
        a = analyze_record(rec, pcache[rec["arch"]])
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"],
            "mesh": rec["mesh"], "kind": rec["kind"],
            "hbm_gib": round(rec["memory"].get(
                "per_device_total_bytes", 0) / 2**30, 2),
            **a,
            "remedy": _REMEDY[a["dominant"]],
        })
    return rows


def run(report):
    rows = build_table()
    for r in rows:
        cell = f"{r['arch']}/{r['shape']}"
        report("roofline", f"{cell}:compute_s", r["compute_s"])
        report("roofline", f"{cell}:memory_s", r["memory_s"])
        report("roofline", f"{cell}:collective_s", r["collective_s"])
        report("roofline", f"{cell}:dominant", r["dominant"])
        report("roofline", f"{cell}:roofline_fraction",
               round(r["roofline_fraction"], 4))
    return rows
