"""Flash-attention block sweep at a given sequence length (VERDICT r2 #7).

Round 2's sweep ran only at S=8192; short sequences are the common case
and @2048 measured ~6 MFU points below @8192.  This sweep times fwd and
fwd+bwd per (block_q, block_k) at any S with the LICM-proof chained-scan
pattern and RTT correction, so `_auto_block` defaults can be set per
length from data.

Usage: python scripts/flash_block_sweep.py --seq 2048 [--quick]
"""

from __future__ import annotations

import argparse
import json
import time


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpudist.ops.flash_attention import flash_attention
    from tpudist.runtime.cache import enable_compilation_cache

    enable_compilation_cache()
    assert jax.default_backend() == "tpu"
    s = args.seq
    b, h, d = 4, 8, 128
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
               for kk in ks)
    fwd_flops = 2 * b * h * s * s * d
    reps_f = (200 if s <= 2048 else 60) if not args.quick else 20
    reps_t = max(reps_f // 4, 4)
    n_win = 3 if args.quick else 5

    f = jax.jit(jnp.sum)
    tiny = jnp.ones((8, 8), jnp.float32)
    float(f(tiny))
    rtt = min(_timed(lambda: float(f(tiny))) for _ in range(8))
    print(json.dumps({"rtt_ms": round(rtt * 1e3, 1), "seq": s}), flush=True)

    blocks = [c for c in (2048, 1024, 512, 256, 128) if c <= s]
    for bq in blocks:
        for bk in blocks:
            if bq * bk > 1024 * 1024:
                continue  # tiles this large exceed the kernel's VMEM

            @jax.jit
            def many_fwd(q, k, v, bq=bq, bk=bk):
                def body(qc, _):
                    out = flash_attention(qc, k, v, causal=True,
                                          block_q=bq, block_k=bk)
                    return out.astype(qc.dtype), None

                return jnp.sum(lax.scan(body, q, None, length=reps_f)[0]
                               .astype(jnp.float32))

            @jax.jit
            def many_train(q, k, v, bq=bq, bk=bk):
                def loss(qc, kc, vc):
                    return jnp.sum(flash_attention(
                        qc, kc, vc, causal=True, block_q=bq,
                        block_k=bk).astype(jnp.float32))

                def body(carry, _):
                    qc, kc, vc = carry
                    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(
                        qc, kc, vc)
                    return ((qc + 1e-3 * dq).astype(qc.dtype),
                            (kc + 1e-3 * dk).astype(kc.dtype),
                            (vc + 1e-3 * dv).astype(vc.dtype)), None

                (qo, _, _), _ = lax.scan(body, (q, k, v), None,
                                         length=reps_t)
                return jnp.sum(qo.astype(jnp.float32))

            rec = {"bq": bq, "bk": bk, "seq": s}
            try:
                float(many_fwd(q, k, v))
                t = min(_timed(lambda: float(many_fwd(q, k, v)))
                        for _ in range(n_win))
                rec["fwd_tflops"] = round(
                    fwd_flops * reps_f / max(t - rtt, t * 0.05) / 1e12, 1)
                float(many_train(q, k, v))
                t = min(_timed(lambda: float(many_train(q, k, v)))
                        for _ in range(n_win))
                rec["train_tflops"] = round(
                    fwd_flops * 4.5 * reps_t / max(t - rtt, t * 0.05)
                    / 1e12, 1)
            except Exception as e:  # noqa: BLE001
                rec["error"] = str(e)[:120]
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
