"""The parts of a ``dsa`` layer at the cell's shapes on the chip, each
alone: the ways to select 2048 of 8192 keys a query EXACTLY
(``select_topk``'s counting passes against ``lax.top_k`` and the scatter
that makes a set of its indices), the indexer's score kernel and its
backward, the selection kernels forward and forward + backward by block
against the dense causal kernel, the head-summed distribution by block;
and one layer's forward + backward under ``jax.checkpoint`` with the
selection kept (``model.py``'s policy) against rebuilt.

    chiprun -- python3 tests/benchmarks/data/keye_controls/bench_dsa.py \\
        chiprun_out/bench_dsa.json

Not a run of the benchmark: a layer alone, no window, no rate (PERF.md
section 6, PR 34, has what it read).
"""
import json, os, sys, time
sys.path.insert(0, os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[".."] * 4)))
import jax, jax.numpy as jnp
from cxxnet_tpu.graph import LayerSpec
from cxxnet_tpu.layers import ApplyCtx, create_layer
from cxxnet_tpu.ops import attention as A

S, D, H, HKV, J, DI, K, E = 8192, 128, 32, 4, 16, 64, 2048, 2048
BF = jnp.bfloat16


def timed(fn, *a, n=5):
    for _ in range(2):
        jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return 1e3 * (time.perf_counter() - t0) / n


out = {"device": jax.devices()[0].device_kind, "rows": []}


def row(**fields):
    out["rows"].append(fields)
    print(fields, flush=True)


def attempt(name, fn, *a, **more):
    try:
        row(what=name, ms=timed(fn, *a), **more)
    except Exception as e:              # what the compiler refuses
        row(what=name, error=repr(e)[:300], **more)


key = lambda n: jax.random.PRNGKey(n)
q = jax.random.normal(key(0), (1, S, H, D), BF)
k = jax.random.normal(key(1), (1, S, HKV, D), BF)
v = jax.random.normal(key(2), (1, S, HKV, D), BF)
w = jax.random.normal(key(3), (1, S, H, D), BF)
qi = jax.random.normal(key(4), (1, S, J, DI), BF)
ki = jax.random.normal(key(5), (1, S, DI), BF)
wt = jax.random.normal(key(6), (1, S, J), jnp.float32) / 32

# the indexer's scores, forward and backward
scores = jax.jit(lambda a, b, c: A.index_scores(a, b, c, 512))(qi, ki, wt)
attempt("index_scores kernel fwd", jax.jit(
    lambda a, b, c: A.index_scores(a, b, c, 512)), qi, ki, wt)
attempt("index_scores jnp chunks fwd", jax.jit(A.index_scores_reference),
        qi, ki, wt)
g = jax.random.normal(key(7), (1, S, S), jnp.float32)
attempt("index_scores bwd (XLA, chunks)", jax.jit(
    lambda a, b, c, g_: jax.vjp(
        lambda *x: A.index_scores(*x, 512), a, b, c)[1](g_)), qi, ki, wt, g)

# the exact selection, two ways
attempt("select_topk (counting passes) -> int8", jax.jit(
    lambda s: A.select_topk(s, K).astype(jnp.int8)), scores)
causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
attempt("lax.top_k alone", jax.jit(
    lambda s: jax.lax.top_k(jnp.where(causal, s, -jnp.inf), K)[1]), scores)


def by_top_k(s):
    _, idx = jax.lax.top_k(jnp.where(causal, s, -jnp.inf), K)
    hit = jnp.zeros(s.shape, jnp.int8).at[
        0, jnp.arange(S)[:, None], idx[0]].set(1)
    return hit * causal.astype(jnp.int8)
attempt("lax.top_k + scatter -> int8", jax.jit(by_top_k), scores)
sel = jax.jit(lambda s: A.select_topk(s, K).astype(jnp.int8))(scores)
try:
    same = bool(jnp.all(jax.jit(by_top_k)(scores) == sel))
    row(what="the two selections are one set", same=same,
        pairs=int(jnp.sum(sel, dtype=jnp.int32)))
except Exception as e:
    row(what="the two selections are one set", error=repr(e)[:300])

# the attention kernels
for b in (512, 1024):
    f = lambda q, k, v, b=b: A.flash_attention_select(
        q, k, v, sel, None, b, b)[0]
    attempt("flash_attention_select fwd", jax.jit(f), q, k, v, block=b)
    attempt("flash_attention_select fwd+bwd", jax.jit(jax.grad(
        lambda q, k, v, f=f: jnp.sum((f(q, k, v) * w).astype(jnp.float32)),
        (0, 1, 2))), q, k, v, block=b)
    lse = jax.jit(lambda q, k, v, b=b: A.flash_attention_select(
        q, k, v, sel, None, b, b)[1])(q, k, v)
    attempt("head_sum_probs", jax.jit(
        lambda q, k, lse, b=b: A.head_sum_probs(q, k, lse, sel, None, b)),
        q, k, lse, block=b)
dense = lambda q, k, v: A.flash_attention(q, k, v, True, None, 1024, 1024)
attempt("flash_attention causal fwd", jax.jit(dense), q, k, v, block=1024)
attempt("flash_attention causal fwd+bwd", jax.jit(jax.grad(
    lambda q, k, v: jnp.sum((dense(q, k, v) * w).astype(jnp.float32)),
    (0, 1, 2))), q, k, v, block=1024)

# one layer, forward + backward, under checkpoint: the selection kept
# (the model's policy) against rebuilt
layer = create_layer(LayerSpec("dsa", "attn", [0], [1], [
    (a, str(b)) for a, b in dict(
        nhead=H, nkvhead=HKV, head_dim=D, qk_norm=1, rope_theta=10000000,
        mrope_section="16,24,24", index_heads=J, index_head_dim=DI,
        index_topk=K, init_sigma=0.02, random_type="gaussian").items()]), [])
params = layer.init_params(key(8), [(E, S, 1)])
state = layer.init_state([(E, S, 1)])
x = jax.random.normal(key(9), (1, S, 1, E), BF)
ctx = ApplyCtx(train=True, compute_dtype=BF)


def apply(p, x_):
    (y,), new = layer.apply(p, state, [x_], ctx)
    return jnp.sum(y.astype(jnp.float32)) + new["_aux_loss"]
policy = jax.checkpoint_policies.save_only_these_names
for what, names in (
        ("selection, output and logsumexp kept",
         A.FLASH_RESIDUALS + (A.SELECT_RESIDUAL,)),
        ("selection rebuilt", A.FLASH_RESIDUALS),
        ("nothing kept", ())):
    attempt("dsa layer fwd+bwd under checkpoint: " + what, jax.jit(jax.grad(
        jax.checkpoint(apply, policy=policy(*names)), (0, 1))), params, x)
attempt("dsa layer fwd alone", jax.jit(apply), params, x)
os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
json.dump(out, open(sys.argv[1], "w"), indent=1)
