"""Roofline analysis per (arch x shape x mesh) from the dry-run artifacts.

Three terms (seconds, per training/serve step, on the single-pod mesh):

  compute    = FLOPs_total / (chips * 197e12)         [bf16 MXU peak]
  memory     = HBM_bytes   / (chips * 819e9)
  collective = collective_bytes / (chips * 50e9)      [per-link ICI]

Methodology note (EXPERIMENTS.md §Roofline): XLA-CPU's cost_analysis counts
while-loop (lax.scan) bodies ONCE and legalizes bf16 temps to f32, so its
"flops"/"bytes" undercount scanned layers and overstate buffer sizes. The
roofline terms therefore come from the explicit analytic cost model below
(the same napkin math the perf loop optimizes); the HLO-parsed collective
bytes and memory_analysis numbers from the dry-run JSONs are reported
alongside as observed per-iteration lower bounds / f32-inflated peaks.

MODEL_FLOPS = 6 * N_active * tokens (the useful-compute yardstick);
ratio = MODEL_FLOPS / FLOPs_total exposes remat/attention/dispatch overhead.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

try:  # run as a script: benchmarks/ is on sys.path
    from peaks import peaks_for
except ImportError:  # imported as benchmarks.roofline (report.py, run.py)
    from benchmarks.peaks import peaks_for
from repro.configs import SHAPES, applicable, get_config, list_archs
from repro.configs.base import ArchConfig

_CHIP = peaks_for("TPU v5 lite")  # the dry-run's target: a v5e pod
PEAK_FLOPS = _CHIP["bf16_flops"]  # per chip
HBM_BW = _CHIP["hbm_gbps"] * 1e9  # B/s per chip
ICI_BW = _CHIP["ici_link_gbps"] * 1e9  # B/s per link
BP = 2  # param bytes (bf16)
BA = 2  # activation bytes (bf16)


@dataclass
class Cost:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    model_flops: float


def _layer_dims(cfg: ArchConfig):
    d, hd = cfg.d_model, cfg.hd
    attn_proj = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
    if cfg.moe is not None:
        ffn = cfg.moe.top_k * 3 * d * cfg.moe.d_ff + d * cfg.moe.n_experts
    elif cfg.mlp in ("swiglu", "geglu"):
        ffn = 3 * d * cfg.d_ff
    elif cfg.mlp == "none":
        ffn = 0
    else:
        ffn = 2 * d * cfg.d_ff
    return attn_proj, ffn


def _attn_layers(cfg: ArchConfig):
    """(n_full_attn_layers, n_local_attn_layers, n_ssm_layers, n_mlstm)."""
    L = cfg.n_layers
    if cfg.family == "hybrid":
        n_shared = len([i for i in range(L) if cfg.shared_attn_every and i % cfg.shared_attn_every == 0])
        return n_shared, 0, L, 0
    if cfg.family == "ssm":
        n_s = len([i for i in range(L) if cfg.slstm_every and i % cfg.slstm_every == cfg.slstm_every - 1])
        return 0, 0, n_s, L - n_s
    if cfg.global_every:
        n_glob = L // cfg.global_every
        return n_glob, L - n_glob, 0, 0
    return L, 0, 0, 0


def _score_flops(cfg, B, S, kind):
    """Attention score+PV flops (fwd)."""
    hd = cfg.hd
    n_full, n_local, n_ssm, n_mlstm = _attn_layers(cfg)
    win = cfg.window or S
    if kind == "decode":
        per_full = 4 * B * S * cfg.n_heads * hd
        per_local = 4 * B * min(win, S) * cfg.n_heads * hd
        per_mlstm = 4 * B * cfg.n_heads * hd * hd  # state matmul
        ssm = n_ssm * 2 * B * (2 * cfg.d_model) * (cfg.ssm.d_state if cfg.ssm else hd)
        return n_full * per_full + n_local * per_local + n_mlstm * per_mlstm + ssm
    # train/prefill (causal => half)
    per_full = 2 * B * S * S * cfg.n_heads * hd
    per_local = 2 * B * S * min(win, S) * cfg.n_heads * hd
    per_mlstm = 2 * B * S * S * cfg.n_heads * hd  # quadratic parallel form
    ssm_flops = 0
    if cfg.ssm is not None:
        h = (cfg.ssm.expand * cfg.d_model) // cfg.ssm.head_dim
        p, n, ch = cfg.ssm.head_dim, cfg.ssm.d_state, cfg.ssm.chunk
        ssm_flops = n_ssm * (2 * B * S * ch * h * p + 4 * B * S * h * p * n)
    if cfg.family == "encdec":
        enc = cfg.n_encoder_layers * 2 * B * cfg.encoder_seq**2 * cfg.n_heads * hd * 2
        cross = cfg.n_layers * 4 * B * S * cfg.encoder_seq * cfg.n_heads * hd
        return n_full * per_full + enc + cross + ssm_flops
    return n_full * per_full + n_local * per_local + n_mlstm * per_mlstm + ssm_flops


def cell_cost(cfg: ArchConfig, shape, mesh_devices: int, microbatches: int = 8) -> Cost:
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    L = cfg.n_layers
    attn_proj, ffn = _layer_dims(cfg)
    P_mm_layer = attn_proj + ffn
    unembed = d * cfg.vocab
    P_total = cfg.n_params()
    P_active = cfg.active_params()

    if shape.kind == "train":
        T = B * S
        fwd = 2 * T * (L * P_mm_layer + unembed) + _score_flops(cfg, B, S, "train")
        flops = 4.0 * fwd  # fwd + bwd(2x) + remat re-fwd(1x)
        A = microbatches
        param_traffic = 3 * A * P_total * BP  # read per microbatch (fwd/bwd/refwd)
        grad_traffic = 3 * P_total * 4
        opt_traffic = 5 * P_total * 4
        act_traffic = 20 * T * d * L * BA
        hbm = param_traffic + grad_traffic + opt_traffic + act_traffic
        # FSDP all-gather 3x per microbatch + grad reduce-scatter per mb + TP
        coll = 3 * A * P_total * BP + A * P_total * BP + 6 * L * T * d * BA
        model_flops = 6.0 * P_active * T
    elif shape.kind == "prefill":
        T = B * S
        fwd = 2 * T * (L * P_mm_layer + unembed) + _score_flops(cfg, B, S, "prefill")
        flops = fwd
        kv_write = 2 * L * T * cfg.n_kv_heads * cfg.hd * BP
        hbm = P_total * BP + 8 * T * d * L * BA + kv_write + _score_flops(cfg, B, S, "prefill") / (2 * cfg.hd) * BA
        coll = P_total * BP + 2 * L * T * d * BA
        model_flops = 2.0 * P_active * T  # forward only
    else:  # decode
        T = B
        fwd = 2 * T * (L * P_mm_layer + unembed) + _score_flops(cfg, B, S, "decode")
        flops = fwd
        n_full, n_local, n_ssm, n_mlstm = _attn_layers(cfg)
        win = cfg.window or S
        kv_read = 2 * (n_full * S + n_local * min(win, S)) * cfg.n_kv_heads * cfg.hd * B * BP
        state_read = 0
        if cfg.ssm is not None:
            h = (cfg.ssm.expand * d) // cfg.ssm.head_dim
            state_read = 2 * n_ssm * B * h * cfg.ssm.head_dim * cfg.ssm.d_state * 4
        if cfg.family == "ssm":
            state_read = 2 * L * B * cfg.n_heads * (2 * d // cfg.n_heads) ** 2 * 4
        hbm = P_active * BP + kv_read + state_read + 4 * T * d * L * BA
        # H3 (measured): GSPMD keeps FSDP-sharded weights stationary at
        # decode and reduces the (tiny) activations instead — collective
        # volume is O(L*B*d) activations + MoE dispatch, NOT O(P).
        coll = 2 * L * T * d * BA
        if cfg.moe is not None:
            coll += 4 * T * cfg.moe.top_k * d * BA  # a2a dispatch+combine
        model_flops = 2.0 * P_active * T  # forward only
    return Cost(flops=flops, hbm_bytes=hbm, coll_bytes=coll, model_flops=model_flops)


def analyze(results_dir: str = "results/dryrun", mesh: str = "single"):
    rows = []
    chips = 256 if mesh == "single" else 512
    for arch in list_archs():
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, why = applicable(cfg, shape)
            rec_path = Path(results_dir) / f"{arch}__{sname}__{mesh}.json"
            rec = json.loads(rec_path.read_text()) if rec_path.exists() else {}
            if not ok:
                rows.append({"arch": arch, "shape": sname, "status": "SKIP", "why": why})
                continue
            mb = rec.get("microbatches", 8)
            c = cell_cost(cfg, shape, chips, microbatches=mb)
            t_comp = c.flops / (chips * PEAK_FLOPS)
            t_mem = c.hbm_bytes / (chips * HBM_BW)
            t_coll = c.coll_bytes / (chips * ICI_BW)
            dom = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
                      key=lambda kv: kv[1])
            bound = t_comp + t_mem + t_coll  # no-overlap step-time bound
            rows.append({
                "arch": arch, "shape": sname, "status": rec.get("status", "?"),
                "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
                "dominant": dom[0],
                "roofline_frac": t_comp / bound if bound > 0 else 0.0,
                "model_flops": c.model_flops,
                "flops_total": c.flops,
                "useful_ratio": c.model_flops / c.flops if c.flops else 0.0,
                "hlo_flops_per_dev_raw": rec.get("flops"),
                "hlo_collective_bytes": rec.get("collective_bytes"),
                "temp_bytes_f32_inflated": rec.get("temp_size_in_bytes"),
                "arg_bytes": rec.get("argument_size_in_bytes"),
                "compile_s": rec.get("compile_s"),
            })
    return rows


def main():
    rows = analyze()
    print("arch,shape,status,dominant,t_compute_s,t_memory_s,t_collective_s,"
          "roofline_frac,useful_ratio")
    for r in rows:
        if r["status"] == "SKIP":
            print(f'{r["arch"]},{r["shape"]},SKIP,,,,,,')
            continue
        print(
            f'{r["arch"]},{r["shape"]},{r["status"]},{r["dominant"]},'
            f'{r["t_compute_s"]:.4g},{r["t_memory_s"]:.4g},'
            f'{r["t_collective_s"]:.4g},{r["roofline_frac"]:.3f},'
            f'{r["useful_ratio"]:.3f}'
        )


if __name__ == "__main__":
    main()
