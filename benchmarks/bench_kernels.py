"""Kernel microbenchmarks: Pallas (interpret) vs the pure-jnp oracle.

On this CPU container the interpreter overhead dominates, so the derived
column reports the analytic VMEM working set / FLOP counts that govern the
TPU target rather than claiming CPU speedups.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_us
from repro.kernels import ops, ref


def run() -> None:
    rng = np.random.default_rng(0)

    # hi_gate over a serving batch of logits
    for n, c in [(1024, 10), (256, 32000)]:
        logits = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        us_k = time_us(lambda: ops.hi_gate(logits, 0.607))
        ref_jit = jax.jit(lambda l: ref.hi_gate_ref(l, 0.607))
        us_r = time_us(lambda: ref_jit(logits))
        emit(f"hi_gate_{n}x{c}", us_k,
             f"oracle {us_r:.0f}us; fuses 4 HBM passes -> 1 "
             f"({n*c*4/1e6:.1f}MB logits)")

    # decode attention over a long cache
    b, s, h, k, d = 4, 4096, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.bfloat16)
    ck = jnp.asarray(rng.normal(size=(b, s, k, d)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(b, s, k, d)), jnp.bfloat16)
    valid = jnp.arange(s) < 3000
    us_k = time_us(lambda: ops.decode_attention(q, ck, cv, valid))
    ref_jit = jax.jit(lambda *a: ref.decode_attention_ref(*a))
    us_r = time_us(lambda: ref_jit(q, ck, cv, valid))
    emit(f"decode_attn_b{b}_s{s}", us_k,
         f"oracle {us_r:.0f}us; VMEM/step {2*512*d*2/1024:.0f}KB "
         f"(vs {2*s*d*2/1e6:.1f}MB unblocked)")

    # SSD chunk kernel
    b, l, hh, p, n = 2, 512, 8, 64, 64
    x = jnp.asarray(rng.normal(size=(b, l, hh, p)), jnp.float32)
    dt = jnp.asarray(rng.random((b, l, hh)), jnp.float32) * 0.5
    A = -jnp.asarray(rng.random(hh), jnp.float32) - 0.2
    B = jnp.asarray(rng.normal(size=(b, l, 1, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, l, 1, n)), jnp.float32)
    us_k = time_us(lambda: ops.ssd(x, dt, A, B, C, chunk=128))
    ref_jit = jax.jit(lambda *a: ref.ssd_ref(*a, chunk=128))
    us_r = time_us(lambda: ref_jit(x, dt, A, B, C))
    emit(f"ssd_b{b}_l{l}_h{hh}", us_k,
         f"oracle {us_r:.0f}us; intra-chunk 128x128 MXU tiles, "
         f"decay buffer bounded to chunk (vs whole-seq in jnp)")
