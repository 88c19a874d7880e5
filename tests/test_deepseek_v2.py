"""DeepSeek-V2's mechanisms at a tiny size on the CPU: YaRN rope scaling
against the published formulas, and the dropless expert layer that holds
a share of the experts (shares add up to the whole layer; a token's
output does not depend on its batch-mates)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ArchConfig
from repro.models.common import materialize, rope, yarn_inv_freq
from repro.models.config import RopeScaling
from repro.models.mla import score_dim, score_scale
from repro.models.moe import _add_shared, moe_ffn, moe_specs

YARN = get_config("deepseek-v2-lite").rope_scaling


# -- (a) YaRN -----------------------------------------------------------------

def _published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, transcribed line by line
    (yarn_find_correction_dim / _range, yarn_linear_ramp_mask), float64."""
    def find_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / \
            (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float64) - low) / (high - low)
    inv_freq_mask = 1.0 - np.clip(linear, 0, 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask, \
        (low, high)


@pytest.mark.parametrize("dim,base", [(64, 1e4), (8, 1e4), (128, 1e6)])
def test_yarn_inv_freq_matches_the_published_formula(dim, base):
    want, (low, high) = _published_inv_freq(dim, base, 40, 4096, 32, 1)
    if (dim, base) == (64, 1e4):
        assert (low, high) == (10, 23)      # DeepSeek-V2-Lite's rope dims
    got = yarn_inv_freq(dim, base, YARN)
    # float32 against float64: a few float32 roundings (1.2e-7 each) of
    # each frequency; a bf16 one would be off by up to 4e-3
    np.testing.assert_allclose(got, want, rtol=5e-7, atol=0)


def test_yarn_softmax_and_cos_sin_factors():
    cfg = get_config("deepseek-v2-lite")
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert math.isclose(mscale ** 2, 1.5896, rel_tol=1e-4)
    assert math.isclose(score_dim(cfg) ** -0.5, 192 ** -0.5 * mscale ** 2,
                        rel_tol=1e-12)
    assert math.isclose(score_scale(cfg), 192 ** -0.5 * mscale ** 2,
                        rel_tol=1e-12)
    # cos/sin factor mscale / mscale_all_dim: 1 at the published values,
    # 1.2608 / 1 when only ``mscale`` is set
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5, dtype=jnp.int32)[None]
    one = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                      mscale=0.707, mscale_all_dim=0.707)
    lone = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                       mscale=1.0)
    r1, r2 = rope(x, pos, 1e4, one), rope(x, pos, 1e4, lone)
    np.testing.assert_allclose(
        r2, r1 * (0.1 * math.log(40) + 1.0), rtol=1e-6, atol=1e-6)


def test_no_rope_scaling_is_bit_identical():
    """Without rope scaling, rope and the MLA score scale are as before."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3, 16))
    pos = jax.random.randint(jax.random.PRNGKey(2), (2, 7), 0, 5000)
    half = 8
    freq = 1e4 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    assert np.array_equal(rope(x, pos, 1e4), want)
    plain = get_config("deepseek-v2-lite").with_(rope_scaling=None)
    assert score_dim(plain) == 192 and score_scale(plain) is None


# -- (b), (c) the dropless expert layer ---------------------------------------

MOE = ArchConfig(name="ds_moe", family="moe", n_layers=1, d_model=32,
                 n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=64,
                 n_experts=16, top_k=4, n_shared_experts=1, d_ff_expert=16,
                 dtype=jnp.float32)


def _layer(cfg, seed=0):
    p = materialize(moe_specs(cfg), jax.random.PRNGKey(seed), jnp.float32)
    # a router wide enough that the top-4 are no near-ties
    p["router"] = p["router"] * 50.0
    return p


def _share(p, lo, hi):
    return {**p, **{n: p[n][lo:hi] for n in ("w_gate", "w_up", "w_down")}}


def _shared_only(p, x, cfg):
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    return _add_shared(p, xf, jnp.zeros_like(xf), cfg).reshape(x.shape)


@pytest.mark.parametrize("norm", [True, False])
def test_expert_shares_add_up_to_the_whole_layer(norm):
    """The four shares (0,4), (4,8), (8,12), (12,16) of a 16-expert layer,
    the shared expert counted once, give the uncut layer's output."""
    cfg = MOE.with_(norm_topk_prob=norm)
    p = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 32))
    mask = jnp.ones((3, 5), bool).at[2, 3:].set(False)
    whole, picks = moe_ffn(p, x, cfg, token_mask=mask)
    shared = _shared_only(p, x, cfg)
    parts, held, touched = [], 0, 0
    for lo in range(0, 16, 4):
        y, c = moe_ffn(_share(p, lo, lo + 4), x,
                       cfg.with_(experts_held=(lo, lo + 4)), token_mask=mask)
        parts.append(y - shared)
        held, touched = held + c[0], touched + c[2]
        assert c[1] == picks[1] == 4 * 13           # 13 tokens, top-4
    assert held == picks[0] == picks[1] and touched == picks[2] <= 16
    total = sum(parts) + shared
    # f32 rounding of a sum of O(1) terms reordered: ~1e-7 relative; a
    # bf16 computation of the same layer is off by ~1e-2
    scale = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(total, whole, rtol=0, atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(total[2, 3:] - shared[2, 3:]))) == 0.0


def test_share_routes_over_all_experts_and_renormalises_only_when_asked():
    """Gates are the softmax over all 16 experts' top-4, renormalised only
    under norm_topk_prob: the two differ by exactly that sum."""
    p = _layer(MOE)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 32))
    mask = jnp.ones((1, 6), bool)
    lo_cfg = MOE.with_(experts_held=(0, 16))
    shared = _shared_only(p, x, MOE)
    raw = moe_ffn(p, x, lo_cfg.with_(norm_topk_prob=False),
                  token_mask=mask)[0] - shared
    normed = moe_ffn(p, x, lo_cfg, token_mask=mask)[0] - shared
    probs = jax.nn.softmax(x.reshape(6, 32) @ p["router"], axis=-1)
    topsum = jax.lax.top_k(probs, 4)[0].sum(-1)
    np.testing.assert_allclose(raw.reshape(6, 32),
                               normed.reshape(6, 32) * topsum[:, None],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_token_output_ignores_its_batch_mates(held):
    """A token's expert output is the same alone and among others that
    crowd its experts, at any capacity factor: nothing is dropped, where
    the capacity-bound training path drops the crowded token's picks."""
    cfg = MOE.with_(experts_held=held, capacity_factor=1.0)
    p = _layer(cfg)
    tok = jax.random.normal(jax.random.PRNGKey(5), (32,))
    crowd = (jnp.broadcast_to(tok, (1, 12, 32)) + 0.01 * jax.random.normal(
        jax.random.PRNGKey(6), (1, 12, 32))).at[0, 11].set(tok)
    others = jax.random.normal(jax.random.PRNGKey(7), (2, 7, 32)) \
        .at[1, 3].set(tok)
    alone = moe_ffn(p, tok[None, None], cfg,
                    token_mask=jnp.ones((1, 1), bool))[0][0, 0]
    for batch, at in ((crowd, (0, 11)), (others, (1, 3))):
        mask = jnp.ones(batch.shape[:2], bool)
        y = moe_ffn(p, batch, cfg, token_mask=mask)[0]
        # the same sums, from matmuls of other row counts: f32 rounding
        # (~1e-7); a dropped or displaced pick moves the row by O(1)
        np.testing.assert_allclose(y[at], alone, rtol=1e-5, atol=1e-5)
        wide = moe_ffn(p, batch, cfg.with_(capacity_factor=100.0),
                       token_mask=mask)[0]
        np.testing.assert_array_equal(y, wide)
    if held == (0, 16):
        # capacity 8 a expert: the 12th of 12 alike tokens loses its picks
        capped = moe_ffn(p, crowd, cfg)[0][0, 11]
        assert float(jnp.max(jnp.abs(capped - alone))) > 0.1


# -- the engine: pick counters, and a latent pool padded to whole tiles --------

def test_engine_counts_picks_without_padding():
    """``stats()`` after serving: every pick of every real token, over
    both expert layers, and a quarter of them held; warm-up and padding
    count nothing."""
    from repro.models import init_params
    from repro.serve import Request, ServeConfig, ServingEngine
    cfg = MOE.with_(n_layers=2, pattern=(("scan", "attn_moe", 2),),
                    experts_held=(4, 8), decode_margin=32)
    eng = ServingEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                        ServeConfig(max_batch=3, max_prompt=8,
                                    max_new_tokens=5, max_seq=24))
    eng.warmup()
    prompts = [[5, 7, 11], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3], [8]]
    eng.run([Request(i, p) for i, p in enumerate(prompts)])
    st = eng.stats()
    rows = {"prefill": sum(map(len, prompts)), "decode": 3 * 4}
    for kind, n in rows.items():
        assert st["expert_picks_total"][kind] == n * 4 * 2    # top-4, 2 layers
        assert 0 < st["expert_picks_held"][kind] < n * 4 * 2
        assert 0 < st["expert_groups_touched"][kind] \
            <= 4 * 2 * st["expert_dispatches"][kind]
    dense = ServingEngine(MOE.with_(n_experts=0, top_k=0, family="dense",
                                    d_ff=32, n_shared_experts=0),
                          None, ServeConfig(max_batch=1, max_prompt=4))
    assert dense.stats() == {}


def test_wide_latent_rows_pad_the_paged_pool():
    """A latent row wider than one 128-lane tile is stored padded to whole
    tiles; the zeros are never read, so paged serving gives the logits of
    the contiguous cache."""
    from repro.models import init_params
    from repro.models.mla import latent_width, paged_mla_cache_spec
    from repro.serve import Request, ServeConfig, ServingEngine
    cfg = ArchConfig(name="wide_mla", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=100,
                     kv_lora_rank=120, qk_nope_dim=16, qk_rope_dim=16,
                     v_head_dim=16, decode_margin=32, dtype=jnp.float32,
                     pattern=(("scan", "mla_mlp", 2),),
                     rope_scaling=YARN)
    assert latent_width(120, 16) == 256 and latent_width(32, 8) == 40
    assert paged_mla_cache_spec(cfg, 4, 8)["ckv"].shape == (4, 8, 256)
    params = init_params(cfg, jax.random.PRNGKey(1))

    def logits(paged):
        eng = ServingEngine(cfg, params, ServeConfig(
            max_batch=2, max_prompt=8, max_new_tokens=4, paged=paged,
            record_logits=True))
        out = eng.run([Request(0, [5, 7, 11, 2, 9, 4, 3, 8]),
                       Request(1, [3, 1])])
        return [np.stack(r.logits) for r in sorted(out, key=lambda r: r.rid)]

    for a, b in zip(logits(True), logits(False)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
