"""Cases of the window call (``ops/window.py``, ``ops/window_kernel.py``) for
the families' test files: rings that hold each row's last ``window`` tokens
and ANOTHER sequence's loud values everywhere else, and the plain oracle,
whole-sequence attention under the explicit causal and window masks."""

import jax.numpy as jnp
import numpy as np

from helix_tpu.ops.attention import mha_reference

# ``window, query heads, rows of (fresh tokens, tokens behind, slot), the
# static bound on a row's fresh tokens (None: the axis), [kv heads, tokens on
# the axis]``: from an empty ring, across a chunk boundary, after the ring has
# wrapped, a row shorter than the window beside one past it, a row longer than
# the window, an unused row
ROWS = {
    "decode_rows_on_both_sides_of_the_wrap": (
        8, 8, [(1, 0, 0), (1, 3, 1), (1, 8, 2), (1, 29, 3)], 1),
    "chunks_from_empty_across_a_boundary_and_wrapped": (
        16, 16, [(5, 0, 0), (12, 3, 1), (16, 16, 2), (9, 40, 3), (0, 0, 4)],
        None),
    "a_row_longer_than_the_window_at_a_group_of_6": (
        8, 12, [(20, 5, 0), (3, 0, 1)], None),
    # the LONG block of a chunk call (``chunk_query_block``: 128 tokens at a
    # query group of 8, what the bucket holds under that), 8 kv heads:
    # a chunk of two blocks and a part of a third
    "a_chunk_longer_than_a_block_and_not_a_multiple_of_it": (
        256, 64, [(300, 300, 0)], 512, 8, 512),
    "a_chunk_shorter_than_a_block_in_a_512_bucket": (
        256, 64, [(20, 300, 1)], 512, 8, 512),
    # queries 0..23 see the whole history, the later ones lose its start; the
    # row is longer than its window, so its own first tokens leave it too
    "a_chunk_that_crosses_the_window_inside_a_block": (
        64, 16, [(100, 40, 0)], 128),
    # the first row's last block is a part: it runs on over the second row's
    # first 106 positions, which that row's own blocks then write
    "two_chunk_rows_the_second_off_a_block_boundary": (
        128, 16, [(150, 0, 0), (200, 200, 1)], 512, 2, 512),
    "one_token_rows_beside_a_chunk_row": (
        128, 16, [(1, 5, 0), (1, 300, 1), (140, 130, 2), (1, 0, 3)], 256),
    "a_long_block_at_a_group_of_6": (64, 12, [(270, 50, 0)], 512, 2, 512),
    "a_long_block_at_a_group_of_2": (
        24, 4, [(300, 30, 0), (7, 24, 1)], 512, 2, 512),
}
# ... and 4 kv heads under a group of 8 over a ring of 1,024 rows, laid out
# by position in eight steps: a wrapped ring whose oldest row lies inside a
# step (the run of rows behind it wraps to the ring's start), a ring its row
# has half written beside a row that starts its sequence, a whole bucket
ROWS_OF_4_KV_HEADS = {
    "a_chunk_over_a_wrapped_ring": (
        1024, 32, [(150, 1400, 0)], 512, 4, 512),
    "a_ring_half_written_and_a_first_chunk_beside_it": (
        1024, 32, [(70, 500, 1), (40, 0, 2)], 512, 4, 512),
    "a_whole_bucket_of_four_blocks": (
        1024, 32, [(512, 1030, 0)], 512, 4),
}


def window_case(window, H, rows, seed=0, KVH=2, T=None, D=128, L=2, layer=1,
                nslots=5):
    """``(the window call's arguments, the rows' whole histories)``."""
    rng = np.random.default_rng(seed)
    T = max(T or 0, sum(r[0] for r in rows) + 3)
    q, kn, vn = (jnp.asarray(rng.standard_normal((T, h, D)), jnp.float32)
                 for h in (H, KVH, KVH))
    hk, hv = ({s: rng.standard_normal((h, KVH, D)).astype(np.float32)
               for _, h, s in rows} for _ in range(2))
    kr, vr = (rng.standard_normal((L, nslots, window, KVH, D)).astype(
        np.float32) * 5 for _ in range(2))
    for _, h, s in rows:
        for p in range(max(0, h - window), h):
            kr[layer, s, p % window] = hk[s][p]
            vr[layer, s, p % window] = hv[s][p]
    t0 = np.cumsum([0] + [r[0] for r in rows[:-1]])
    meta = [jnp.asarray(x, jnp.int32) for x in (
        t0, [r[0] for r in rows], [r[1] for r in rows],
        [r[2] for r in rows])]
    return (q, kn, vn, jnp.asarray(kr), jnp.asarray(vr), layer, *meta), (
        hk, hv, t0)


def plain(args, hist, rows, window):
    """Whole-sequence attention under the explicit causal and window masks,
    a row at a time: ``{row's first token: out}``."""
    q, kn, vn = args[:3]
    hk, hv, t0 = hist
    out = {}
    for (n, h, s), a in zip(rows, t0):
        if n:
            out[a] = mha_reference(
                q[a:a + n][None],
                jnp.concatenate([jnp.asarray(hk[s]), kn[a:a + n]])[None],
                jnp.concatenate([jnp.asarray(hv[s]), vn[a:a + n]])[None],
                causal=True, q_positions=jnp.arange(h, h + n)[None],
                kv_positions=jnp.arange(h + n)[None], window=window)[0]
    return out


def held_to_the_plain_oracle(call, case, tol=1e-5):
    """``call(*args, max_q_len=)`` on ``case`` against ``plain``: float32 both
    sides, another order of the softmax's sums."""
    window, H, rows, mq, *more = case
    args, hist = window_case(window, H, rows, *([0, *more] if more else []))
    got = call(*args, max_q_len=mq)
    for a, want in plain(args, hist, rows, window).items():
        assert float(jnp.abs(got[a:a + len(want)] - want).max()) < tol
