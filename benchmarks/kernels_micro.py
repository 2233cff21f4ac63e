"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp reference, plus
the fused-vs-eager counterfactual from the energy model (§6.2/§7.2).

Wall-times here are CPU-interpret numbers (correctness-path); the *derived*
column reports the modelled TPU-side effect of fusion, which is the claim
that matters: fused MLA decode removes the kernel zoo, fused SSD/GDN
prefill collapses the order-of-magnitude eager penalty.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs.paper_models import PAPER_MODELS
from repro.core import Default, decode_workload, prefill_workload, resolve
from repro.kernels import (
    decode_attention,
    decode_attention_ref,
    gdn_prefill,
    gdn_scan_ref,
    mla_latent_decode,
    mla_latent_decode_ref,
    ssd_prefill,
    ssd_scan_ref,
)

from benchmarks.common import Row, h200_model, timed, write_csv


def _bench(fn, *args, iters=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> list[Row]:
    key = jax.random.PRNGKey(0)
    rows: list[Row] = []
    csv_rows = []
    emodel = h200_model()

    # --- decode_attn ------------------------------------------------------
    B, H, KV, D, L = 2, 8, 2, 64, 512
    q = jax.random.normal(key, (B, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, KV, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, KV, D))
    vl = jnp.full((B,), L, jnp.int32)
    # the kernel reads a one-layer stack: L - 1 cached rows and the new row
    us_k = _bench(decode_attention, q, k[:, -1], v[:, -1], k[None], v[None], jnp.int32(0),
                  vl - 1, jnp.ones((B,), bool), scale=0.125, interpret=True)
    us_r = _bench(decode_attention_ref, q, k, v, vl, 0.125)
    csv_rows.append(["decode_attn", us_k, us_r])
    rows.append(("kernel_decode_attn", us_k, f"ref_us={us_r:.0f};interpret=True"))

    # --- mla_decode + modelled zoo elimination -----------------------------
    ql = jax.random.normal(key, (B, 16, 64))
    qr = jax.random.normal(jax.random.fold_in(key, 3), (B, 16, 16))
    ckv = jax.random.normal(jax.random.fold_in(key, 4), (B, L, 64))
    kr = jax.random.normal(jax.random.fold_in(key, 5), (B, L, 16))
    us_k = _bench(mla_latent_decode, ql, qr, ckv, kr, vl, scale=0.11, block_l=128, interpret=True)
    us_r = _bench(mla_latent_decode_ref, ql, qr, ckv, kr, vl, 0.11)
    mla = PAPER_MODELS["minitron-4b-mla"]()
    eager = resolve(emodel, decode_workload(mla, 1, 1024), Default())
    fused = resolve(emodel, decode_workload(mla, 1, 1024, fused=True), Default())
    gain = 1 - fused.energy_per_token_mj / eager.energy_per_token_mj
    csv_rows.append(["mla_decode", us_k, us_r])
    rows.append((
        "kernel_mla_decode", us_k,
        f"ref_us={us_r:.0f};modelled_decode_energy_gain={gain:.1%}",
    ))

    # --- ssd ---------------------------------------------------------------
    b, s, h, p, n = 1, 256, 8, 32, 64
    x = jax.random.normal(key, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 6), (b, s, h)))
    a = -jnp.exp(jnp.linspace(-2, 0.5, h))
    bm = jax.random.normal(jax.random.fold_in(key, 7), (b, s, n)) * 0.3
    cm = jax.random.normal(jax.random.fold_in(key, 8), (b, s, n)) * 0.3
    us_k = _bench(ssd_prefill, x, dt, a, bm, cm, q_chunk=64, head_block=4, interpret=True)
    us_r = _bench(ssd_scan_ref, x, dt, a, bm, cm)
    m2 = PAPER_MODELS["mamba2-4b"]()
    e_eager = resolve(emodel, prefill_workload(m2, 1, 4096), Default()).energy_per_token_mj
    e_fused = resolve(emodel, prefill_workload(m2, 1, 4096, fused=True), Default()).energy_per_token_mj
    csv_rows.append(["ssd_prefill", us_k, us_r])
    rows.append((
        "kernel_ssd", us_k,
        f"ref_us={us_r:.0f};modelled_prefill_mj {e_eager:.1f}->{e_fused:.1f}",
    ))

    # --- gdn ----------------------------------------------------------------
    q2 = jax.random.normal(key, (1, 128, 4, 32))
    q2 = q2 / jnp.linalg.norm(q2, axis=-1, keepdims=True)
    k2 = jax.random.normal(jax.random.fold_in(key, 9), (1, 128, 4, 32))
    k2 = k2 / jnp.linalg.norm(k2, axis=-1, keepdims=True)
    v2 = jax.random.normal(jax.random.fold_in(key, 10), (1, 128, 4, 32)) * 0.5
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 11), (1, 128, 4)))
    alpha = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 12), (1, 128, 4)) + 2)
    us_k = _bench(gdn_prefill, q2, k2, v2, beta, alpha, q_chunk=32, interpret=True)
    us_r = _bench(gdn_scan_ref, q2, k2, v2, beta, alpha)
    gdn = PAPER_MODELS["gdn-4b"]()
    e_eager = resolve(emodel, prefill_workload(gdn, 1, 4096), Default()).energy_per_token_mj
    e_fused = resolve(emodel, prefill_workload(gdn, 1, 4096, fused=True), Default()).energy_per_token_mj
    csv_rows.append(["gdn_prefill", us_k, us_r])
    rows.append((
        "kernel_gdn", us_k,
        f"ref_us={us_r:.0f};modelled_prefill_mj {e_eager:.1f}->{e_fused:.1f} ({e_eager/e_fused:.1f}x)",
    ))

    write_csv("kernels_micro", ["kernel", "pallas_interpret_us", "ref_us"], csv_rows)
    return rows
