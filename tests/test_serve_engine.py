"""Serving-engine equivalence: continuous batching must not change tokens.

The contract of ``serve.engine`` + ``serve.scheduler`` is that scheduling is
*invisible* in the output stream: every request decodes exactly the tokens it
would have produced served solo through the stock jitted prefill/decode path,
no matter how requests are packed into slots, how rounds are bucketed, when
neighbours are admitted or evicted, or whether a long prompt prefilled
chunked. These tests pin that bit-identity for attention (paged KV), MLA
(paged latent KV) and mamba2 (dense per-slot state) block types.

Configs use float32: under bf16, jit fusion can round two near-tied logits
equal where the eager/solo path keeps them one ULP apart, flipping argmax —
the reference must then match rounding mode, not just math. f32 makes ties
astronomically unlikely, so the comparison tests scheduling, not rounding.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.models.common import LMConfig, MLACfg, SSMCfg
from repro.serve import kv_pages
from repro.serve.engine import Engine
from repro.serve import scheduler as sch


def _mk_cfg(pattern, **kw):
    base = dict(
        arch_id="serve-test",
        d_model=48,
        n_layers=2,
        vocab=96,
        n_heads=4,
        n_kv_heads=2,
        head_dim=12,
        d_ff=96,
        dtype=jnp.float32,
        pattern=pattern,
    )
    base.update(kw)
    return LMConfig(**base)


CFGS = {
    "attn": _mk_cfg((("dense", 2),)),
    "mla": _mk_cfg(
        (("mla_dense", 2),),
        mla=MLACfg(kv_lora_rank=24, qk_nope_dim=12, qk_rope_dim=8, v_head_dim=12),
    ),
    "mamba2": _mk_cfg(
        (("mamba2", 2),),
        ssm=SSMCfg(d_state=16, d_conv=4, expand=2, head_dim=12, chunk=8),
    ),
}


def _params(cfg):
    return lm.init_params(cfg, jax.random.PRNGKey(0))


def _solo_tokens(cfg, params, prompt: np.ndarray, out_len: int) -> list:
    """Greedy tokens from the stock JITTED solo path (batch 1, dense caches).
    Jitted, not eager: the engine's rounds are jitted, and jit is allowed to
    round differently from eager — the reference must share the compile."""
    L = int(prompt.shape[0])
    prefill = jax.jit(lambda p, x: lm.prefill(cfg, p, x))
    decode = jax.jit(lambda p, t, c, pos: lm.decode_step(cfg, p, t, c, pos))
    logits, caches = prefill(params, jnp.asarray(prompt, jnp.int32)[None, :])
    caches = lm.unstack_caches(cfg, caches)
    caches = kv_pages.grow_caches(cfg, caches, L + out_len)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [int(tok[0])]
    for i in range(out_len - 1):
        logits, caches = decode(params, tok, caches, jnp.int32(L + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


def _mk_trace(cfg, seed, n, prompt_lens, out_lens):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        L = int(rng.choice(prompt_lens))
        reqs.append(sch.Request(
            rid=i, arrival=0.0,
            tokens=rng.integers(0, cfg.vocab, size=L).astype(np.int32),
            out_len=int(rng.choice(out_lens)),
        ))
    return reqs


@pytest.mark.parametrize("kind", sorted(CFGS))
@pytest.mark.parametrize("policy", ["continuous", "static"])
def test_engine_matches_solo_serving(kind, policy):
    """More requests than slots: admission waits on evictions, pages recycle,
    rounds run with heterogeneous neighbours — tokens must not notice."""
    cfg = CFGS[kind]
    params = _params(cfg)
    trace = _mk_trace(cfg, seed=3, n=5, prompt_lens=(4, 6), out_lens=(2, 5, 8))
    eng = Engine(cfg, params, n_slots=3, max_seq=16, page=4)
    res = sch.run_trace({"default": eng}, trace, policy=policy)
    assert len(res["requests"]) == len(trace)
    by_rid = {r.rid: r for r in res["requests"]}
    for req in trace:
        got = by_rid[req.rid].tokens
        want = _solo_tokens(cfg, params, req.tokens, req.out_len)
        assert got == want, f"{kind}/{policy} rid={req.rid}: {got} != {want}"


def test_chunked_prefill_matches_single_shot():
    cfg = CFGS["attn"]
    params = _params(cfg)
    prompt = np.random.default_rng(7).integers(0, cfg.vocab, size=12).astype(np.int32)
    outs = {}
    for chunk in (None, 4):
        eng = Engine(cfg, params, n_slots=2, max_seq=32, page=4, chunk_size=chunk)
        job = eng.start(prompt)
        assert job.chunked == (chunk is not None)
        n_calls = 0
        while not job.finished:
            eng.prefill_step(job)
            n_calls += 1
        if chunk:
            assert n_calls == 3  # 12 tokens / chunk 4
        _, first = eng.admit(job)
        toks, _ = eng.decode_round(4)
        outs[chunk] = [first] + [int(toks[i, 0]) for i in range(4)]
    assert outs[4] == outs[None]


def test_admit_evict_any_order_recycles_pages():
    """Interleaved admit/evict in arbitrary slot order: pages recycle through
    the free list and later tenants are unaffected by previous occupants."""
    cfg = CFGS["attn"]
    params = _params(cfg)
    rng = np.random.default_rng(11)
    # pool sized for exactly 2 concurrent tenants at full length: recycling
    # is load-bearing, not incidental
    eng = Engine(cfg, params, n_slots=2, max_seq=16, page=4, num_pages=8)
    total = eng.alloc.free_pages()

    def serve_one(L, out_len):
        prompt = rng.integers(0, cfg.vocab, size=L).astype(np.int32)
        job = eng.start(prompt)
        while not job.finished:
            eng.prefill_step(job)
        slot, first = eng.admit(job)
        got = [first]
        while len(got) < out_len:
            toks, _ = eng.decode_round(2)
            got += [int(toks[i, slot]) for i in range(min(2, out_len - len(got)))]
        return slot, prompt, got

    s0, p0, g0 = serve_one(6, 5)
    s1, p1, g1 = serve_one(4, 3)
    assert s0 != s1
    eng.evict(s0)  # evict the FIRST tenant; the second keeps decoding
    s2, p2, g2 = serve_one(6, 5)
    assert s2 == s0  # slot (and its recycled pages) reused
    eng.evict(s1)
    eng.evict(s2)
    assert eng.alloc.free_pages() == total  # every page returned
    # third tenant's tokens match solo serving despite slot/page reuse under
    # a live neighbour (g1's rounds ran interleaved with g2's history)
    assert g2 == _solo_tokens(cfg, params, p2, 5)
    assert g0 == _solo_tokens(cfg, params, p0, 5)


def test_engine_under_mesh_matches_solo():
    """The engine on a 1-device mesh (sharded page pools) must produce the
    same tokens as the unsharded path."""
    cfg = CFGS["attn"]
    params = _params(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    trace = _mk_trace(cfg, seed=5, n=3, prompt_lens=(4, 6), out_lens=(3, 6))
    results = {}
    for name, m in (("host", None), ("mesh", mesh)):
        eng = Engine(cfg, params, n_slots=2, max_seq=16, page=4, mesh=m)
        res = sch.run_trace({"default": eng}, trace, policy="continuous")
        results[name] = {r.rid: r.tokens for r in res["requests"]}
    assert results["mesh"] == results["host"]


def test_sla_tiers_route_and_share_clock():
    """Two engines (different cost scales) on one clock: every request lands
    on its tier's engine, and the pricier tier's tokens cost more time."""
    cfg = CFGS["attn"]
    params = _params(cfg)
    rng = np.random.default_rng(9)
    reqs = []
    for i, tier in enumerate(["premium", "bulk"] * 2):
        reqs.append(sch.Request(
            rid=i, arrival=0.0,
            tokens=rng.integers(0, cfg.vocab, size=4).astype(np.int32),
            out_len=4, tier=tier,
        ))
    costs = {}
    engines = {
        "premium": Engine(cfg, params, n_slots=2, max_seq=16, page=4,
                          costs=costs, cost_scale=4.0),
        "bulk": Engine(cfg, params, n_slots=2, max_seq=16, page=4,
                       costs=costs, cost_scale=1.0),
    }
    res = sch.run_trace(engines, reqs, policy="continuous")
    assert {r.rid for r in res["requests"]} == {0, 1, 2, 3}
    for r in res["requests"]:
        want = _solo_tokens(cfg, params, reqs[r.rid].tokens, reqs[r.rid].out_len)
        assert r.tokens == want
    # same model, same per-shape cost table: the 4x cost scale must show up
    # in the premium tier's per-token latency
    p = [r for r in res["requests"] if r.tier == "premium"]
    b = [r for r in res["requests"] if r.tier == "bulk"]
    p_itl = np.mean([np.diff(r.token_times).mean() for r in p])
    b_itl = np.mean([np.diff(r.token_times).mean() for r in b])
    assert p_itl > b_itl


def test_unrouted_tier_raises():
    cfg = CFGS["attn"]
    params = _params(cfg)
    eng = Engine(cfg, params, n_slots=2, max_seq=16, page=4)
    req = sch.Request(rid=0, arrival=0.0,
                      tokens=np.zeros(4, np.int32), out_len=2, tier="gold")
    with pytest.raises(ValueError, match="unrouted"):
        sch.run_trace({"default": eng}, [req])
