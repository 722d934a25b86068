"""Roofline analysis (assignment deliverable g) from the dry-run artifacts.

For every (arch × shape × mesh) cell:

  compute_s    = dot_flops_per_device / 197 TFLOP/s        (bf16 MXU peak)
  memory_s     = tpu_bytes_per_device / 819 GB/s            (HBM)
  collective_s = Σ_kind factor·bytes / 50 GB/s              (ICI per link)

dot_flops / bytes come from the trip-count-corrected HLO census
(launch/hlo_census.py) — ``cost_analysis()`` counts loop bodies once and is
reported only as a cross-check.  ``tpu_bytes`` is the fusion-optimistic
traffic model (dots, gathers/scatters, slices, in-place DUS, collectives);
the raw CPU-scheduled byte count is an upper bound (CPU HLO barely fuses).

Collective traffic factors per device: all-reduce 2× result (ring, 2(n-1)/n),
all-gather 1× result (result IS the moved payload), reduce-scatter 16×
result (result is the shard; group size ≈16 on the dp axis — documented
approximation), all-to-all / permute 1×.

MODEL_FLOPS uses 6·N_active·tokens (train) / 2·N_active·tokens (+ attention
context term) so the MODEL/HLO ratio exposes remat recompute, causal-waste
and kv-replication overheads.
"""

from __future__ import annotations

import json
import pathlib

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9
COLL_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 16.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def active_params(cfg) -> float:
    """Params touched per token (MoE: top-k + shared experts only)."""
    from repro.models import Model

    total = Model(cfg).param_count()
    if cfg.moe is None:
        return float(total)
    m = cfg.moe
    n_mats = 3 if cfg.act == "swiglu" else 2
    per_expert = n_mats * cfg.d_model * m.d_expert
    n_moe_layers = sum(cfg.layer_is_moe())
    routed_total = m.n_experts * per_expert * n_moe_layers
    routed_active = m.experts_per_token * per_expert * n_moe_layers
    return float(total - routed_total + routed_active)


def model_flops_per_device(cfg, shape, n_dev: int) -> float:
    n_act = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        flops = 6.0 * n_act * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops = 2.0 * n_act * tokens
    else:  # decode: one token per sequence + attention over the cache
        tokens = shape.global_batch
        flops = 2.0 * n_act * tokens
    # attention context term (score+pv): 4 · tokens · S_ctx · H · hd per attn layer
    hd = cfg.resolved_head_dim if cfg.num_heads else 0
    n_attn = sum(1 for k in cfg.block_kinds() if k in ("attn", "mla"))
    if n_attn and hd:
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        sctx = shape.seq_len
        causal = 0.5 if shape.kind != "decode" else 1.0
        mult = 3.0 if shape.kind == "train" else 1.0
        flops += mult * causal * 4.0 * tokens * sctx * cfg.num_heads * hd * n_attn
    return flops / n_dev


def analyze(dryrun_dir: str = "experiments/dryrun") -> list[dict]:
    from repro.configs import SHAPES, get_config

    out = []
    for path in sorted(pathlib.Path(dryrun_dir).glob("*.json")):
        rec = json.loads(path.read_text())
        if rec.get("status") != "ok":
            out.append(rec)
            continue
        cfg = get_config(rec["arch"])
        shape = SHAPES[rec["shape"]]
        n_dev = rec["n_devices"]
        compute_s = rec["flops_per_device"] / PEAK_FLOPS
        memory_s = rec["tpu_bytes_per_device"] / HBM_BW
        coll = rec["collectives"]["per_kind"]
        coll_s = sum(COLL_FACTOR[k] * v["bytes"] for k, v in coll.items()) / LINK_BW
        terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
        dominant = max(terms, key=terms.get)
        mf = model_flops_per_device(cfg, shape, n_dev)
        ratio = mf / rec["flops_per_device"] if rec["flops_per_device"] else 0.0
        bound_s = max(terms.values())
        rec.update(
            compute_s=compute_s,
            memory_s=memory_s,
            collective_s=coll_s,
            dominant=dominant,
            model_flops_per_device=mf,
            useful_flops_ratio=ratio,
            roofline_fraction=(mf / PEAK_FLOPS) / bound_s if bound_s else 0.0,
            advice=_advice(dominant, rec, ratio),
        )
        out.append(rec)
    return out


def _advice(dominant: str, rec: dict, ratio: float) -> str:
    if dominant == "compute":
        if ratio < 0.5:
            return (
                "compute-bound but <50% useful: cut remat recompute (policy), "
                "causal-block skipping (Pallas flash), or kv-replication waste"
            )
        return "compute-bound and mostly useful flops: increase arithmetic intensity won't help; this is healthy"
    if dominant == "memory":
        return "HBM-bound: fuse elementwise chains, keep params bf16, widen batch per device to amortize weight reads"
    return "collective-bound: reshard to cut all-gathers (FSDP prefetch), overlap via microbatch pipelining, or move the axis with less traffic to the slower links"


def markdown_table(recs: list[dict]) -> str:
    hdr = (
        "| arch | shape | mesh | compute_s | memory_s | collective_s | dominant | "
        "MODEL/HLO flops | roofline_frac | peak_GB/dev |\n|---|---|---|---|---|---|---|---|---|---|"
    )
    lines = [hdr]
    for r in recs:
        if r.get("status") == "skip":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — | skip | — | — | — |"
            )
            continue
        if r.get("status") != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | FAIL | | | | | | |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} | {r['collective_s']:.3f} "
            f"| **{r['dominant']}** | {r['useful_flops_ratio']:.2f} "
            f"| {r['roofline_fraction']:.2f} | {r['memory']['peak_bytes_est'] / 2**30:.1f} |"
        )
    return "\n".join(lines)


def run(dryrun_dir: str = "experiments/dryrun") -> list[tuple[str, float, str]]:
    recs = analyze(dryrun_dir)
    ok = [r for r in recs if r.get("status") == "ok"]
    rows = []
    for r in ok:
        name = f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}"
        bound = max(r["compute_s"], r["memory_s"], r["collective_s"])
        rows.append(
            (
                name,
                bound * 1e6,
                f"dom={r['dominant']};frac={r['roofline_fraction']:.2f};useful={r['useful_flops_ratio']:.2f}",
            )
        )
    pathlib.Path("experiments").mkdir(exist_ok=True)
    pathlib.Path("experiments/roofline.md").write_text(markdown_table(recs))
    return rows


if __name__ == "__main__":
    # CSV rows like every bench, not the markdown table (run() still
    # writes the md table to experiments/roofline.md).
    import sys

    for r in run(*sys.argv[1:2]):
        print(",".join(map(str, r)))
