"""The gqa kernel at the cell's shapes on the chip: forward alone and
forward + backward, by block, full and window layers; and the sum of a
key/value head's gradient over its query heads alone.

    chiprun -- python3 tests/benchmarks/data/laguna_controls/bench_attn.py \\
        chiprun_out/bench_attn.json

Not a run of the benchmark: a layer alone, no window, no rate (PERF.md
section 6, PR 32, has what it read).
"""
import json, os, sys, time
sys.path.insert(0, os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[".."] * 4)))
import jax, jax.numpy as jnp
from cxxnet_tpu.ops.attention import flash_attention, flash_tiles

S, D, HKV = 8192, 128, 4


def timed(fn, *a, n=10):
    for _ in range(2):
        jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return 1e3 * (time.perf_counter() - t0) / n


out = {"device": jax.devices()[0].device_kind, "rows": []}
k0 = jax.random.PRNGKey(0)
for heads, window, blocks in ((36, 512, (128, 256, 512, 1024)),
                              (24, None, (512, 1024)),
                              (36, None, (1024,))):
    q = jax.random.normal(k0, (1, S, heads, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, S, HKV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, S, HKV, D), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (1, S, heads, D), jnp.bfloat16)
    for b in blocks:
        f = lambda q, k, v: flash_attention(q, k, v, True, None, b, b, None, window)
        fwd = jax.jit(f)
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            (f(q, k, v) * w).astype(jnp.float32)), (0, 1, 2)))
        try:
            row = {"heads": heads, "window": window, "block": b,
                   "tiles": flash_tiles(S, b, window),
                   "fwd_ms": timed(fwd, q, k, v),
                   "fwd_bwd_ms": timed(both, q, k, v)}
        except Exception as e:          # a block the compiler refuses
            row = {"heads": heads, "window": window, "block": b,
                   "error": repr(e)[:300]}
        out["rows"].append(row)
        print(row, flush=True)
    # the sum over a group alone, as the backward makes it
    G = heads // HKV
    part = jax.random.normal(k0, (HKV, G, S, D), jnp.float32)
    red = jax.jit(lambda a, b: (jnp.sum(a, 1).astype(jnp.bfloat16),
                                jnp.sum(b, 1).astype(jnp.bfloat16)))
    row = {"heads": heads, "group_sum_dk_dv_ms": timed(red, part, part + 1)}
    out["rows"].append(row)
    print(row, flush=True)
os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
json.dump(out, open(sys.argv[1], "w"), indent=1)
