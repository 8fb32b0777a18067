"""Parity suite for the unified ragged paged-attention step (ISSUE 10).

Three layers of evidence that the one-kernel collapse changed nothing
observable:

1. **Op level**: the Pallas ragged kernel (interpret mode) matches the
   ``ops/paged.py`` gather reference on randomized ragged layouts
   covering every caller shape — decode rows, verify-width rows, chunk
   rows, packed rows with and without history — × int8 pools.
2. **Engine level**: greedy outputs through every caller shape (packed
   prefill, chunked prefill, the mixed step, spec-verify, prefix-cache
   chunk-hit) match the full-forward oracle — the same oracle the
   pre-unification engine was pinned to, so transitively the greedy
   outputs are the pre-unification outputs (verified bit-for-bit
   against the pre-unification engine when this suite was introduced).
3. **Structural**: the compiled-shape registry stays O(|token ladder|)
   for a workload that exercises every caller, padding flows through
   the single ``_charge_padding`` site, and a prompt admitted COLD
   equals the same prompt admitted as a cache HIT (two different caller
   shapes, one answer) — × int8.

The fast lane keeps one test per axis (each caller shape, each pool
dtype, the structural bounds); the exhaustive randomized sweeps and the
warmup-ladder compile check are slow-marked.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helix_tpu.engine.engine import Engine, EngineConfig, Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import forward, init_params, prefill_attn_fn
from helix_tpu.ops.paged import (
    ragged_paged_attention_reference,
)
from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu


@pytest.fixture(scope="module")
def tiny_model():
    cfg = ModelConfig.tiny(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    return cfg, params


def _make_engine(cfg, params, **extra):
    defaults = dict(
        max_decode_batch=4, page_size=4, num_pages=128,
        max_pages_per_seq=16, max_prefill_len=16,
        attn_backend="reference",
    )
    defaults.update(extra)
    return Engine(cfg, params, EngineConfig(**defaults))


_ORACLE_FNS: dict = {}
_ORACLE_BUCKET = 64


def _oracle_fn(cfg):
    """One jitted full-forward at a FIXED padded length: causal masking
    makes trailing padding invisible to earlier positions, so every
    oracle step shares one compiled shape (the per-length retrace was
    the old oracle's dominant cost)."""
    fn = _ORACLE_FNS.get(cfg)
    if fn is None:
        @jax.jit
        def fn(params, tokens, positions):
            logits, _ = forward(
                params, cfg, tokens, positions,
                attn_fn=lambda q, k, v, c, p: prefill_attn_fn(
                    q, k, v, c, p, backend="reference"
                ),
            )
            return logits
        _ORACLE_FNS[cfg] = fn
    return fn


def _oracle_greedy(cfg, params, prompt, n_steps):
    """Greedy generation via full forward over the growing sequence —
    the oracle the pre-unification engine was pinned to."""
    fn = _oracle_fn(cfg)
    toks = list(prompt)
    out = []
    pos = jnp.arange(_ORACLE_BUCKET)[None]
    for _ in range(n_steps):
        L = len(toks)
        assert L <= _ORACLE_BUCKET
        t = np.zeros((1, _ORACLE_BUCKET), np.int32)
        t[0, :L] = toks
        logits = fn(params, jnp.asarray(t), pos)
        nxt = int(jnp.argmax(logits[0, L - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


# ---------------------------------------------------------------------------
# 1. op level: pallas kernel ≡ gather reference
# ---------------------------------------------------------------------------


def _random_layout(rng_np, R, maxP, P, N):
    """A random ragged layout: rows with random q_len (0 = parked),
    random history lengths and shuffled page tables."""
    q_lens = rng_np.integers(0, 6, size=R)
    t0 = np.zeros(R, np.int32)
    cursor = 0
    for r in range(R):
        t0[r] = cursor
        cursor += int(q_lens[r])
    T = max(int(cursor), 1)
    hist = rng_np.integers(0, maxP * P - 8, size=R).astype(np.int32)
    tables = np.zeros((R, maxP), np.int32)
    pages = rng_np.permutation(np.arange(1, N))[: R * maxP]
    tables[:] = pages[: R * maxP].reshape(R, maxP)
    return T, t0, q_lens.astype(np.int32), hist, tables


def _pools(key, shape, int8: bool):
    """(k_pages, v_pages, scales): f32 pools of ``shape``, or their int8
    codes with the packed scale pools the kernel reads."""
    from helix_tpu.ops.quant import pack_scale_pages, quantize_kv

    k_f = jax.random.normal(key, shape, jnp.float32)
    v_f = k_f * 0.5 - 0.25
    if not int8:
        return k_f, v_f, {}
    k_pages, k_scale = quantize_kv(k_f)
    v_pages, v_scale = quantize_kv(v_f)
    return k_pages, v_pages, dict(
        k_scale=pack_scale_pages(k_scale), v_scale=pack_scale_pages(v_scale))


def _assert_kernel_matches_reference(keys, T, H, KVH, D, pools, layer,
                                     t0, q_len, hist, tables, pack=1,
                                     kernel=ragged_paged_attention_tpu,
                                     **kernel_kw):
    """Interpret-mode kernel against the gather reference, row by row.
    ``pack``: kv heads of width ``D`` a 128-lane tile of the pool the KERNEL
    is handed (``ops/paged.py::pack_heads``); the reference reads the same
    pool as ``[P, KVH, D]``, packing nothing."""
    k_pages, v_pages, scales = pools
    q = jax.random.normal(keys[0], (T, H, D), jnp.float32)
    k_new = jax.random.normal(keys[1], (T, KVH, D), jnp.float32)
    v_new = jax.random.normal(keys[2], (T, KVH, D), jnp.float32)
    meta = (jnp.int32(layer),
            *(jnp.asarray(x, jnp.int32) for x in (t0, q_len, hist, tables)))
    args = (q, k_new, v_new, k_pages, v_pages, *meta)
    want = ragged_paged_attention_reference(*args, **scales)
    if pack > 1:
        from helix_tpu.ops.paged import pack_heads, unpack_heads

        packed = k_pages.shape[:3] + (KVH // pack, pack * D)
        got = unpack_heads(kernel(
            *pack_heads(q, k_new, v_new, pack), k_pages.reshape(packed),
            v_pages.reshape(packed), *meta, scale=D ** -0.5,
            interpret=True, **kernel_kw), pack, KVH)
    else:
        got = kernel(*args, interpret=True, **scales, **kernel_kw)
    for r in range(len(q_len)):
        s0, ql = int(t0[r]), int(q_len[r])
        if ql == 0:
            continue
        np.testing.assert_allclose(
            np.asarray(got[s0:s0 + ql]), np.asarray(want[s0:s0 + ql]),
            atol=1e-5,
            err_msg=f"row {r} (t0={s0}, q_len={ql}, hist={hist[r]})",
        )


def _op_case(rng, *, int8: bool, seed: int):
    L, N, P, KVH, D, H, maxP, R = 2, 24, 4, 2, 16, 4, 4, 5
    ks = jax.random.split(jax.random.fold_in(rng, seed), 4)
    pools = _pools(ks[0], (L, N, P, KVH, D), int8)
    T, t0, q_len, hist, tables = _random_layout(
        np.random.default_rng(seed), R, maxP, P, N)
    _assert_kernel_matches_reference(
        ks[1:], T, H, KVH, D, pools, seed % L, t0, q_len, hist, tables)


# The decode layout (one flat position a slot, ``q_len`` 0 for a parked
# slot) under the static one-token bound the engine passes, and one layout
# that mixes one-token and longer rows under the 8-token block.  Pages of
# 16 tokens: a chunk is 128 tokens, so histories cross chunk edges.
_LAYOUTS = {  # name: (q_len a row, history a row, static bound)
    "all_rows_one_token": ([1] * 6, [5, 130, 77, 300, 19, 250], 1),
    "parked_rows_between_live": (
        [1, 0, 1, 0, 0, 1], [40, 99, 200, 7, 0, 131], 1),
    "rows_without_history": ([1] * 6, [0, 64, 0, 1, 129, 0], 1),
    "history_ends_mid_page": ([1] * 6, [37, 1, 15, 17, 143, 305], 1),
    "history_ends_on_a_chunk_edge": (
        [1] * 6, [128, 256, 127, 129, 16, 255], 1),
    "history_of_max_pages": ([1] * 6, [320, 3, 320, 0, 319, 320], 1),
    "first_rows_parked": ([0, 0, 1, 1, 0, 1], [9, 9, 140, 0, 9, 260], 1),
    "one_token_and_longer_rows": (
        [1, 5, 1, 12, 0, 1], [200, 3, 0, 131, 50, 320], 12),
    # fresh keys come 128 a step for the 8-token block: a row of several
    "a_row_of_several_key_blocks": ([3, 150, 1], [40, 131, 0], 150),
}


def _layout_case(rng, name, *, int8: bool):
    q_len, hist, bound = _LAYOUTS[name]
    L, P, KVH, D, H, maxP = 2, 16, 2, 16, 4, 20
    R = len(q_len)
    N = R * maxP + 1
    ks = jax.random.split(jax.random.fold_in(rng, len(name)), 4)
    pools = _pools(ks[0], (L, N, P, KVH, D), int8)
    if bound == 1:
        t0 = np.arange(R)                 # a slot keeps its flat position
        T = R
    else:
        t0 = np.cumsum([0] + q_len[:-1])
        T = int(sum(q_len))
    tables = np.random.default_rng(len(name)).permutation(
        np.arange(1, N))[: R * maxP].reshape(R, maxP)
    _assert_kernel_matches_reference(
        ks[1:], T, H, KVH, D, pools, 1, t0, q_len, hist, tables,
        max_q_len=bound)


# A chunk row's LONG block (``paged_query_block`` over 8 tokens:
# ``_long_block``): (query heads, kv heads, head width, page, pages a table,
# [(fresh tokens, history, flat positions skipped before the row)], static
# bound, pool, kv heads a lane tile, history step | None for the module's).
# Steps of 128 tokens keep the histories short: 256 ends on a step's edge,
# 300 crosses two.
_LONG_ROWS = {
    # 4 + 2 + 2 + 1 blocks of 128 tokens, rows at unaligned offsets
    "rows_of_512_256_136_and_9_tokens_at_a_group_of_8": (
        16, 2, 64, 16, 20,
        [(512, 300, 3), (256, 256, 5), (136, 0, 1), (9, 37, 6)],
        512, "float32", 1, 128),
    "a_group_of_7_padded_to_8": (
        14, 2, 32, 16, 20, [(136, 200, 0), (9, 50, 3)], 136, "float32", 1,
        128),
    "a_group_of_6_padded_to_8": (
        12, 2, 32, 16, 20, [(136, 256, 2), (9, 0, 0)], 136, "float32", 1,
        128),
    "a_group_of_4_padded_to_8": (
        8, 2, 32, 16, 20, [(136, 129, 0), (9, 300, 1)], 136, "float32", 1,
        128),
    "a_group_of_16_in_blocks_of_64": (
        32, 2, 32, 16, 20, [(136, 300, 0), (9, 128, 7)], 136, "float32", 1,
        128),
    "two_kv_heads_of_width_64_a_lane_tile": (
        8, 4, 64, 16, 20, [(136, 270, 0), (40, 0, 3)], 136, "float32", 2,
        128),
    "an_int8_pool": (
        16, 2, 32, 16, 20, [(200, 300, 0), (9, 256, 5)], 200, "int8", 1, 128),
    # eight rows share a 128-token bucket: blocks of 16 tokens
    "a_wave_of_short_rows_with_history": (
        16, 2, 32, 16, 20,
        [(5, 0, 0), (40, 130, 0), (1, 17, 0), (16, 128, 0), (9, 300, 0),
         (3, 64, 0), (30, 5, 0), (8, 200, 0)], 128, "float32", 1, 128),
    # a head of two lane tiles (Qwen3-Next: 16 / 2 / 256): the rows past a
    # step's end are zeroed where they land, in the steps that hold any
    # (histories on a step's edge, inside one, none; an int8 pool widens
    # its codes first)
    "a_head_of_256_lanes_at_a_group_of_8": (
        16, 2, 256, 16, 20, [(136, 300, 3), (130, 256, 5), (9, 0, 1)], 136,
        "float32", 1, 128),
    "a_head_of_256_lanes_over_an_int8_pool": (
        16, 2, 256, 16, 20, [(136, 270, 0), (9, 128, 2)], 136, "int8", 1,
        128),
    # the module's own step (1,024 tokens, fresh keys 512 a step) over pages
    # of 128: two whole steps and a part of a third
    "a_history_of_three_steps_at_the_modules_width": (
        16, 2, 32, 128, 24, [(136, 2348, 0)], 136, "float32", 1, None),
}


def _long_rows_case(rng, name, monkeypatch):
    from helix_tpu.ops import paged_kernel

    H, KVH, D, P, maxP, rows, bound, pool, pack, step = _LONG_ROWS[name]
    if step:
        monkeypatch.setattr(paged_kernel, "LONG_STEP_TOKENS", step)
        monkeypatch.setattr(paged_kernel, "LONG_FRESH_TOKENS", step)
    L, R = 2, len(rows)
    N = R * maxP + 1
    q_len = [n for n, _, _ in rows]
    t0 = np.cumsum([skip + (rows[r - 1][0] if r else 0)
                    for r, (_, _, skip) in enumerate(rows)])
    T = int(t0[-1] + q_len[-1])
    if len(rows) > 4:
        T = bound                      # a wave: the bucket's flat tokens
    ks = jax.random.split(jax.random.fold_in(rng, len(name)), 4)
    pools = _pools(ks[0], (L, N, P, KVH, D), pool == "int8")
    tables = np.random.default_rng(len(name)).permutation(
        np.arange(1, N))[: R * maxP].reshape(R, maxP)
    assert paged_kernel.paged_query_block(
        min(bound, T), H * pack // KVH, R, T) > 8
    # (not the jitted entry: a module constant is read when a call is traced)
    _assert_kernel_matches_reference(
        ks[1:], T, H, KVH, D, pools, 1, t0, q_len, [h for _, h, _ in rows],
        tables, pack=pack,
        kernel=ragged_paged_attention_tpu.__wrapped__, max_q_len=bound)


# the programs a call's grid walks (``live_query_blocks``: the most a
# segment of these tokens and rows can make) under the engine's shapes:
# (bound on a row's tokens, group, rows, flat tokens) -> (block, programs)
_BLOCKS = {
    "decode_rows": ((1, 7, 32, 32), (1, 32)),
    "verify_rows_of_5": ((5, 7, 32, 160), (8, 20 + 32)),
    "one_chunk_row_of_512": ((512, 8, 1, 512), (128, 4 + 1)),
    "one_chunk_row_at_a_group_of_6": ((512, 6, 1, 512), (128, 4 + 1)),
    "one_chunk_row_at_a_group_of_4": ((512, 4, 1, 512), (128, 4 + 1)),
    "one_chunk_row_at_a_group_of_16": ((512, 16, 1, 512), (64, 8 + 1)),
    "a_final_chunk_in_a_bucket_of_128": ((128, 8, 1, 128), (128, 1 + 1)),
    "a_final_chunk_in_a_bucket_of_8": ((8, 8, 1, 8), (8, 1 + 1)),
    # a wave's rows share its tokens: the power of two at or above
    # tokens / rows, and never more than 2 x rows programs
    "a_wave_of_32_rows_in_64_tokens": ((64, 8, 32, 64), (8, 8 + 32)),
    "a_wave_of_32_rows_in_512_tokens": ((512, 8, 32, 512), (16, 32 + 32)),
    "a_wave_of_12_rows_in_512_tokens": ((512, 8, 12, 512), (64, 8 + 12)),
    "a_wave_of_2_rows_in_512_tokens": ((512, 8, 2, 512), (128, 4 + 2)),
}

# sha256 of ``ragged_paged_attention_tpu.lower(...).as_text()`` (interpret
# mode, no locations, under ``jax.default_matmul_precision("highest")`` as
# ``conftest.py`` sets it) at commit 637cd06, BEFORE the long block: (flat
# tokens, rows, bound, pool) of a decode call, a verify call of 5-token
# rows and a wave of 32 rows in a 64-token bucket
_LOWERED_AT_THE_PARENT = {
    (6, 6, 1, "bfloat16"): "5446ccb9529ea00d",
    (6, 6, 1, "int8"): "0910d30b6d4daf93",
    (30, 6, 5, "bfloat16"): "680db32a41f86f84",
    (30, 6, 5, "int8"): "e644a144412c4449",
    (64, 32, 64, "bfloat16"): "0f0aa2d68427590a",
}


class TestRaggedOpParity:
    @pytest.mark.parametrize("name", sorted(_LONG_ROWS))
    def test_long_block_matches_reference(self, rng, name, monkeypatch):
        """A chunk row's long query block, one kv head at a time over wide
        history steps: every group a cell sends, packed heads, an int8 pool,
        rows at unaligned offsets, histories of 0, on a step's edge and
        across several steps, a wave of short rows."""
        _long_rows_case(rng, name, monkeypatch)

    @pytest.mark.parametrize("name", sorted(_BLOCKS))
    def test_block_and_programs_follow_what_a_call_sees(self, name):
        """``paged_query_block`` from the static facts of a call, and the
        programs its grid then walks: a 512-token chunk row is 4 to 8
        programs (64 under the 8-token block), and a wave of short rows does
        not pay a long block a row."""
        from helix_tpu.ops.paged_kernel import (
            chunk_query_block, live_query_blocks, paged_query_block,
        )

        (bound, group, rows, tokens), (block, programs) = _BLOCKS[name]
        assert paged_query_block(bound, group, rows, tokens) == block
        q_len = jnp.zeros((rows,), jnp.int32).at[0].set(min(bound, tokens))
        brow, bidx = live_query_blocks(q_len, block, tokens)
        assert brow.shape == (programs,)
        live = -(-min(bound, tokens) // block)
        assert int((brow >= 0).sum()) == live
        assert list(np.asarray(bidx[:live])) == list(range(live))
        if rows > 1 and block < chunk_query_block(bound, group):
            assert programs <= 2 * rows

    @pytest.mark.parametrize(
        "case", sorted(_LOWERED_AT_THE_PARENT), ids=lambda c: "-".join(
            str(x) for x in c))
    def test_short_rows_lower_to_the_text_they_lowered_to(self, case):
        """The one-token resident form and the 8-token form are untouched by
        the long block: a decode call, a verify call and a wave of rows that
        can only be short lower to the parent's text, byte for byte."""
        import hashlib

        T, R, bound, pool = case
        H, KVH, D, L, N, P, maxP = 8, 2, 128, 2, 40, 16, 12
        S = jax.ShapeDtypeStruct
        pages = S((L, N, P, KVH, D), jnp.dtype(pool))
        scales = {}
        if pool == "int8":
            scales = dict(k_scale=S((L, N, KVH * P), jnp.float32),
                          v_scale=S((L, N, KVH * P), jnp.float32))
        i32 = lambda *shape: S(shape, jnp.int32)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            text = ragged_paged_attention_tpu.lower(
                S((T, H, D), jnp.bfloat16), S((T, KVH, D), jnp.bfloat16),
                S((T, KVH, D), jnp.bfloat16), pages, pages, i32(), i32(R),
                i32(R), i32(R), i32(R, maxP), max_q_len=bound,
                interpret=True, **scales).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
            _LOWERED_AT_THE_PARENT[case])

    @pytest.mark.parametrize("pool", ["float32", "int8"])
    @pytest.mark.parametrize("name", sorted(_LAYOUTS))
    def test_kernel_matches_reference_at_the_static_bound(
            self, rng, name, pool):
        """The block shape follows the static bound on a row's fresh
        tokens: one-token blocks for the decode layout, 8-token blocks
        where a row may be longer; one row contract, one answer."""
        _layout_case(rng, name, int8=pool == "int8")

    def test_query_block_follows_the_static_bound(self):
        from helix_tpu.ops.paged_kernel import paged_query_block, query_block

        assert query_block(1) == 1
        assert [query_block(n) for n in (2, 4, 8, 512)] == [8] * 4
        # this kernel's own: rows that can only be short keep those two
        assert paged_query_block(1, 7, 32, 32) == 1
        assert [paged_query_block(n, 7, 32, 32 * n) for n in (2, 4, 8)] == [
            8] * 3

    def test_kernel_matches_reference_random_layout(self, rng):
        """One randomized ragged layout through interpret-mode pallas
        vs the gather reference (fast lane; the sweep is slow)."""
        _op_case(rng, int8=False, seed=3)

    def test_kernel_matches_reference_int8(self, rng):
        _op_case(rng, int8=True, seed=5)

    @pytest.mark.slow
    def test_kernel_reference_randomized_sweep(self, rng):
        """Exhaustive-ish randomized sweep: many layouts × both pool
        dtypes (decode rows, verify widths, chunk-sized rows, parked
        rows all occur by construction)."""
        for seed in range(12):
            _op_case(rng, int8=seed % 2 == 1, seed=seed)


# ---------------------------------------------------------------------------
# 2. engine level: every caller shape ≡ the full-forward oracle
# ---------------------------------------------------------------------------


class TestEngineCallerShapes:
    N_TOK = 8

    def test_packed_and_decode(self, tiny_model):
        cfg, params = tiny_model
        eng = _make_engine(cfg, params)
        prompts = [[1, 2, 3, 4, 5], [10, 11, 12], [7, 3]]
        got = eng.generate(
            prompts, SamplingParams(temperature=0.0, max_tokens=self.N_TOK)
        )
        for p, g in zip(prompts, got):
            assert g == _oracle_greedy(cfg, params, p, self.N_TOK)

    def test_chunked_prefill(self, tiny_model):
        cfg, params = tiny_model
        eng = _make_engine(cfg, params)
        prompt = [(3 * i) % 29 + 1 for i in range(24)]   # > max_prefill_len
        got = eng.generate(
            [prompt], SamplingParams(temperature=0.0, max_tokens=self.N_TOK)
        )
        assert got[0] == _oracle_greedy(cfg, params, prompt, self.N_TOK)

    def test_mixed_step(self, tiny_model):
        """A long prompt admitted while another request decodes: the
        chunk and the decode rows share one unified call and neither
        perturbs the other."""
        cfg, params = tiny_model
        eng = _make_engine(cfg, params, enable_mixed_step=True)
        r1 = Request(
            id="r1", prompt_tokens=[1, 2, 3, 4, 5],
            sampling=SamplingParams(temperature=0.0, max_tokens=10),
        )
        eng.add_request(r1)
        for _ in range(3):
            eng.step()
        long_prompt = [(5 * i) % 23 + 1 for i in range(24)]
        r2 = Request(
            id="r2", prompt_tokens=long_prompt,
            sampling=SamplingParams(temperature=0.0, max_tokens=self.N_TOK),
        )
        eng.add_request(r2)
        while eng.has_work():
            eng.step()
        assert eng.num_mixed_steps > 0
        assert r1.output_tokens == _oracle_greedy(cfg, params,
                                                  r1.prompt_tokens, 10)
        assert r2.output_tokens == _oracle_greedy(cfg, params,
                                                  long_prompt, self.N_TOK)

    def test_spec_verify(self, tiny_model):
        """Spec-verify rows (ragged draft widths) emit exactly the
        greedy stream, with real acceptance."""
        cfg, params = tiny_model
        eng = _make_engine(cfg, params, enable_spec_decode=True,
                           spec_tokens=3)
        rep = [4, 9, 7, 3] * 4
        got = eng.generate(
            [rep], SamplingParams(temperature=0.0, max_tokens=8)
        )
        assert eng.num_spec_steps > 0
        assert got[0] == _oracle_greedy(cfg, params, rep, 8)

    @pytest.mark.parametrize("kv", ["auto", "int8"])
    def test_cold_vs_cache_hit_same_output(self, tiny_model, kv):
        """The SAME prompt through two different caller shapes — cold
        packed admission vs prefix-cache chunk-hit (remainder attends
        shared pages) — must produce identical tokens, × int8 KV."""
        cfg, params = tiny_model
        eng = _make_engine(cfg, params, kv_cache_dtype=kv)
        prefix = [(7 * i) % 19 + 1 for i in range(12)]
        prompt = prefix + [2, 8]
        sp = SamplingParams(temperature=0.0, max_tokens=self.N_TOK)
        cold = eng.generate([prompt], sp)
        hits0 = eng.prefix_cache_hits
        warm = eng.generate([prompt], sp)
        assert eng.prefix_cache_hits > hits0   # second pass really hit
        assert warm == cold

    @pytest.mark.slow
    def test_exhaustive_caller_grid(self, tiny_model):
        """Caller shapes × kv dtype × prefix-hit, all against the
        oracle (the fast lane covers each axis once; this sweeps the
        cross product)."""
        cfg, params = tiny_model
        long_prompt = [(11 * i) % 27 + 1 for i in range(40)]
        short_prompt = [5, 9, 2, 14]
        for kv in ("auto", "int8"):
            for spec in (False, True):
                eng = _make_engine(
                    cfg, params, kv_cache_dtype=kv,
                    enable_spec_decode=spec, spec_tokens=3,
                )
                sp = SamplingParams(temperature=0.0, max_tokens=6)
                a = eng.generate([short_prompt, long_prompt], sp)
                b = eng.generate([short_prompt, long_prompt], sp)  # hits
                assert a == b, (kv, spec)
                if kv == "auto":
                    assert a[0] == _oracle_greedy(
                        cfg, params, short_prompt, 6
                    )
                    assert a[1] == _oracle_greedy(
                        cfg, params, long_prompt, 6
                    )


# ---------------------------------------------------------------------------
# 3. structural: shape-zoo collapse + single padding site observable
# ---------------------------------------------------------------------------


class TestShapeCollapse:
    def test_compiled_shapes_bounded_across_callers(self, tiny_model):
        """A workload exercising every caller (packed, chunk, mixed,
        spec, hits, fused windows) compiles a handful of entry points —
        bounded by the token ladder, NOT by the caller count.  The
        pre-unification zoo compiled one family per caller × its bucket
        grid (packed buckets + chunk C×hist pairs + mixed pairs +
        per-window decode scans + verify width×hist×tail)."""
        cfg, params = tiny_model
        # page_size distinct from every other engine in the test session:
        # the compiled-shape registry is shared per (model, page geometry)
        # exactly like the traces, so a private geometry gives this test
        # a clean count
        eng = _make_engine(
            cfg, params, enable_spec_decode=True, spec_tokens=3,
            enable_mixed_step=True, max_decode_batch=4, page_size=8,
            max_pages_per_seq=8,
        )
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        long_prompt = [(3 * i) % 29 + 1 for i in range(24)]
        eng.generate([[1, 2, 3], [4, 5, 6, 7], [4, 9, 7, 3] * 5], sp)
        eng.generate([long_prompt, [8, 8, 1]], sp)      # chunk + mixed + hit
        total = eng.compiled_step_shapes
        # ladder for max_prefill_len=16 / page 4 = {4, 8, 16} → worst
        # case: 3 wave rungs (× hist variant) + chunk single-row shapes
        # + the decode-only entry.  The zoo this replaced compiled more
        # for the same workload (6 builders × their grids).  The
        # registry is shared per (model, backend) — exactly like the
        # traces — so the bound holds across every engine of this model
        # in the process.
        assert 0 < total <= 12, total
        # a second identical workload compiles NOTHING new
        eng.generate([[1, 2, 3], [4, 9, 7, 3] * 5], sp)
        assert eng.compiled_step_shapes == total

    def test_padding_single_site(self, tiny_model):
        """Padding accounting flows through Engine._charge_padding: the
        counter moves exactly by (bucket - used) per prefill call, and a
        packed wave charges ONE bucket for the whole wave (the
        pre-unification chunk-hit path charged per request)."""
        cfg, params = tiny_model
        eng = _make_engine(cfg, params)
        assert eng.num_prefill_padding_tokens == 0
        # two 5-token prompts pack into one wave: bucket(10) = 16 on the
        # {4, 8, 16} ladder → ONE charge of 6, not two charges of 3
        eng.generate(
            [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]],
            SamplingParams(temperature=0.0, max_tokens=2),
        )
        assert eng.num_prefill_padding_tokens == 16 - 10
        # and ONE device call for the wave, one for the decode step:
        # 12 tokens in 2 calls
        assert eng.num_prefill_tokens + eng.num_decode_tokens == 10 + 2
        assert eng.num_device_calls == 2

    @pytest.mark.slow
    def test_warmup_compiles_ladder_ahead_of_traffic(self, tiny_model):
        """After warmup, a mixed workload (hits, chunks, decode) mints
        at most the ragged-final-chunk shape — nothing else compiles
        under traffic."""
        cfg, params = tiny_model
        eng = _make_engine(cfg, params, max_pages_per_seq=16)
        eng.warmup()
        warmed = eng.compiled_step_shapes
        assert warmed > 0
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        eng.generate([[1, 2, 3], [4, 5, 6, 7, 8]], sp)
        eng.generate([[1, 2, 3]], sp)   # prefix hit
        long_prompt = [(3 * i) % 29 + 1 for i in range(24)]
        eng.generate([long_prompt], sp)
        grown = eng.compiled_step_shapes - warmed
        # the ragged final chunk (40 % 16 = 8-token tail, single-row) is
        # the one documented post-warmup compile
        assert grown <= 1, grown
