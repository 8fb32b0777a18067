"""Speculative decoding: drafter correctness + distribution preservation.

The load-bearing guarantees (ISSUE 5):

1. **Greedy bit-identity** — with ``enable_spec_decode=True`` every
   request's emitted token sequence is exactly the spec-off sequence at
   temperature 0, whatever the drafter proposes.  The verify call samples
   each position from the slot's own tiers (argmax at temp 0) and accepts
   the longest agreeing prefix, so a wrong draft can change *which device
   call* produced a token, never the token itself.
2. **Sampled-path preservation** — "sample from the target and compare"
   IS rejection sampling for a point-mass draft: the emitted token at
   every position is a true target-distribution draw.  Tested two ways:
   deterministically (an oracle drafter that always proposes the plain
   path's own continuation must reproduce a seeded temp>0 sequence
   bit-for-bit, which pins logits parity, sampler parity, AND key-stream
   parity at every drafted position), and statistically (pooled output
   histograms spec-on vs spec-off, TV-compared like the
   ``test_sampling_exact`` harness).
3. **Worst-case degradation** — an adversarial (never-accepted) drafter
   leaves output AND device-step count identical to spec-off (every
   verify call still emits its bonus token) and the per-request
   acceptance EMA benches the slot after a handful of misses.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from helix_tpu.engine.engine import Engine, EngineConfig, Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.engine.spec import SpecConfig, SpecDecoder, propose
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import init_params


@pytest.fixture(scope="module")
def tiny_model():
    cfg = ModelConfig.tiny(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    return cfg, params


def make_engine(cfg, params, spec, **kw):
    rng_seed = kw.pop("rng_seed", 0)
    ecfg = EngineConfig(
        max_decode_batch=kw.pop("max_decode_batch", 4),
        page_size=4,
        num_pages=kw.pop("num_pages", 128),
        max_pages_per_seq=32,
        max_prefill_len=kw.pop("max_prefill_len", 16),
        enable_spec_decode=spec,
        spec_tokens=kw.pop("spec_tokens", 3),
        **kw,
    )
    return Engine(cfg, params, ecfg, rng_seed=rng_seed)


REP = [5, 6, 7, 8] * 6          # pure repetition: drafts hit
MIX = [9, 3, 1, 4, 1, 5, 9, 2]  # short, mildly repetitive
ADV = [2, 11, 23, 31, 47]       # short, nothing to match


class TestDrafter:
    """Pure-host prompt-lookup drafting (no jax)."""

    def test_proposes_continuation_of_last_match(self):
        # trailing [1, 2] last occurred at index 4 -> continuation [9, 9]
        assert propose([1, 2, 7, 8, 1, 2, 9, 9, 1, 2], 2) == [9, 9]

    def test_longest_ngram_wins(self):
        # trailing 2-gram [3, 4] matches at one place; the 1-gram [4]
        # also occurs later — the 2-gram match must win
        toks = [3, 4, 8, 8, 4, 5, 5, 3, 4]
        assert propose(toks, 1, max_ngram=4) == [8]

    def test_most_recent_occurrence_wins(self):
        # [1, 2] occurs twice; the later occurrence's continuation wins
        toks = [1, 2, 7, 0, 1, 2, 9, 0, 1, 2]
        assert propose(toks, 1) == [9]

    def test_overlapping_self_repetition(self):
        # "abcabc" + trailing "abc": the heart of prompt-lookup — the
        # trailing n-gram overlaps its own earlier occurrence
        toks = [1, 2, 3, 1, 2, 3, 1, 2, 3]
        assert propose(toks, 3) == [1, 2, 3]

    def test_no_match_returns_empty(self):
        assert propose([1, 2, 3, 4, 5], 4) == []

    def test_k_caps_continuation(self):
        assert propose([1, 2, 9, 8, 7, 6, 1, 2], 2) == [9, 8]

    def test_k_zero_and_tiny_sequences(self):
        assert propose([1, 2, 3], 0) == []
        assert propose([1], 4) == []
        assert propose([], 4) == []

    def test_ema_disables_after_misses_then_reprobes(self):
        sd = SpecDecoder(SpecConfig(
            spec_tokens=4, disable_below=0.3, ema_alpha=0.5,
            reprobe_after=3,
        ))
        toks = [1, 2, 3] * 8
        # two full misses: EMA 1.0 -> 0.5 -> 0.25 < 0.3 -> disabled
        for _ in range(2):
            d = sd.draft("r", toks, 4)
            assert d
            sd.observe("r", len(d), 0)
        assert sd.disabled_count() == 1
        assert not sd.enabled("r")
        # cooldown: the next reprobe_after-1 opportunities draft nothing
        assert sd.draft("r", toks, 4) == []
        assert sd.draft("r", toks, 4) == []
        # re-probe: drafting resumes right at the floor
        assert sd.draft("r", toks, 4) != []
        # a hit climbs back above the floor and stays enabled
        sd.observe("r", 4, 4)
        assert sd.enabled("r")
        assert sd.disabled_count() == 0

    def test_forget_drops_state(self):
        sd = SpecDecoder()
        sd.observe("r", 4, 0)
        sd.forget("r")
        assert sd._slots == {}


class TestGreedyEquivalence:
    """Spec-on output must be bit-identical to spec-off at temperature 0,
    with real acceptance (the spec path must actually engage)."""

    def test_greedy_bit_identical_with_acceptance(self, tiny_model):
        cfg, params = tiny_model
        prompts = [REP, MIX, REP[1:]]
        # default single-step decode keeps this tier-1 test under the
        # 20 s line; the fused-window (decode_steps_per_sync) axis runs
        # in the slow composition test below
        sp = [
            SamplingParams(temperature=0.0, max_tokens=24),
            SamplingParams(temperature=0.0, max_tokens=24, seed=123),
            SamplingParams(temperature=0.0, max_tokens=20),
        ]

        def run(spec):
            eng = make_engine(cfg, params, spec)
            reqs = [
                Request(id=f"r{i}", prompt_tokens=list(p), sampling=s)
                for i, (p, s) in enumerate(zip(prompts, sp))
            ]
            for r in reqs:
                eng.add_request(r)
            while eng.has_work():
                eng.step()
            return [r.output_tokens for r in reqs], eng

        base, eng_off = run(False)
        spec, eng_on = run(True)
        assert spec == base
        # non-vacuous: drafts were proposed AND accepted
        assert eng_on.num_spec_drafted_tokens > 0
        assert eng_on.num_spec_accepted_tokens > 0
        assert eng_on.num_spec_steps > 0
        # the whole point: fewer forward passes than tokens decoded
        assert (
            eng_on.num_decode_device_steps
            < eng_off.num_decode_device_steps
        )
        # every accepted draft is also counted as a decode token
        assert eng_on.num_decode_tokens == eng_off.num_decode_tokens

    def test_prefix_cache_shared_pages_stay_safe(self, tiny_model):
        """A request whose prompt prefix is served from the prefix cache
        still speculates: the invariant assert in _spec_step (drafted KV
        never lands in shared pages) must hold, and outputs must match a
        cold-cache spec-off run."""
        cfg, params = tiny_model
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        eng = make_engine(cfg, params, True)
        o1 = eng.generate([REP], sp)
        assert eng.prefix_cache_misses >= 1
        o2 = eng.generate([REP], sp)   # second run claims shared pages
        assert eng.prefix_cache_hits >= 1
        assert o1 == o2
        assert eng.num_spec_accepted_tokens > 0
        off = make_engine(cfg, params, False)
        assert off.generate([REP], sp) == o1


class TestVerifyBesideAChunk:
    """The verify rows (W = 8 a slot) ride the same pass as a prefill
    segment: they accept what they accept in a program of their own."""

    def _ready(self, tiny_model):
        cfg, params = tiny_model
        eng = make_engine(cfg, params, True, spec_tokens=7,
                          max_decode_batch=3, max_prefill_len=32)
        for i, p in enumerate((REP, MIX, REP[2:])):
            eng.add_request(Request(
                id=f"r{i}", prompt_tokens=list(p),
                sampling=SamplingParams(temperature=0.0, max_tokens=40)))
        eng.step()
        eng._sync_state()
        return eng

    def test_a_verify_step_beside_a_chunk_accepts_what_it_accepted(
            self, tiny_model):
        import joint_pass

        cfg, params = tiny_model
        # what each sequence goes on to say, from a plain engine
        plain = make_engine(cfg, params, False, max_decode_batch=3)
        outs = plain.generate(
            [REP, MIX, REP[2:]],
            SamplingParams(temperature=0.0, max_tokens=12))
        got = {}
        for beside in (False, True):
            eng = self._ready(tiny_model)
            assert eng._spec_width() == 8
            drafts = np.zeros((3, 7), np.int32)
            for i, req in enumerate(eng.slots):
                n = len(req.output_tokens)
                drafts[i] = outs[int(req.id[1:])][n:n + 7]
            drafts[1, 3] += 1           # slot 1: the fourth draft is wrong
            draft_len = np.asarray([7, 7, 0], np.int32)
            plan = joint_pass.dummy_plan(eng, 16, 1) if beside else None
            n_joint = eng.num_joint_pass_steps
            _, sampled, emit, _ = eng._ragged_step(
                "mixed" if beside else "spec", plan=plan, drafts=drafts,
                draft_len=draft_len, n_extra=0)
            got[beside] = (np.asarray(sampled), np.asarray(emit),
                           joint_pass.decode_state_of(eng))
            assert eng.num_joint_pass_steps == n_joint + int(beside)
        (s0, e0, st0), (s1, e1, st1) = got[False], got[True]
        assert e0.tolist() == [8, 4, 1] and e1.tolist() == e0.tolist()
        for b in range(3):
            assert s0[b, :e0[b]].tolist() == s1[b, :e1[b]].tolist()
        for k in st0:
            assert np.array_equal(st0[k], st1[k]), k

    def test_a_row_under_speculation_decodes_one_plain_token_in_a_wave(
            self, tiny_model):
        """An admission wave beside two rows under speculation: each
        decodes ONE plain token in it (its eight-wide row has one live
        position), that token is fetched ahead of the drafting (a spec
        engine reconciles before it dispatches), so the step behind the
        wave verifies drafts as it did, and the rows go on being accepted
        as before: the streams are a plain engine's, with the wave's rows
        live or sitting out."""
        import joint_pass

        cfg, params = tiny_model
        prompts = (REP, MIX, REP[2:])
        sp = SamplingParams(temperature=0.0, max_tokens=24)
        plain = make_engine(cfg, params, False, max_decode_batch=3)
        outs = plain.generate(list(prompts), sp)
        after = {}
        for live in (True, False):
            eng = make_engine(cfg, params, True, spec_tokens=7,
                              max_decode_batch=3, max_prefill_len=32)
            if not live:
                eng._wave_rows = lambda: []
            reqs = [Request(id=f"r{i}", prompt_tokens=list(p), sampling=sp)
                    for i, p in enumerate(prompts)]
            eng.add_request(reqs[0])
            eng.add_request(reqs[1])
            eng.step()
            eng.step()
            had = [len(r.output_tokens) for r in reqs[:2]]
            eng.add_request(reqs[2])
            seen: list = []
            with joint_pass.watched_programs(seen):
                eng.step()
            wave, step = seen
            assert wave["rung"] and not step["rung"]
            want = [0, 0, -1] if live else [-1, -1, -1]
            assert wave["draft_len"].tolist() == want
            moved = (wave["after"][0]["positions"]
                     - wave["before"][0]["positions"])
            assert moved.tolist() == [int(live), int(live), 0]
            # the step behind the wave keeps its verify: the wave's
            # tokens are on the host before the drafter reads the rows
            assert not eng._pending_waves
            assert (step["draft_len"][:2] > 0).any(), step["draft_len"]
            got = [len(r.output_tokens) - n for r, n in zip(reqs, had)]
            assert all(g >= 1 + int(live) for g in got[:2]), got
            accepted = eng.num_spec_accepted_tokens
            while eng.has_work():
                eng.step()
            assert [r.output_tokens for r in reqs] == outs
            after[live] = eng.num_spec_accepted_tokens - accepted
            assert eng.num_wave_decode_tokens == 2 * int(live)
        # the drafter's hits on the repeated suffix go on after the wave
        assert after[True] > 0 and after[False] > 0

    def test_the_verify_rows_and_the_chunk_share_one_forward(
            self, tiny_model):
        import joint_pass

        eng = self._ready(tiny_model)
        joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "mlp.down")


class TestDistributionPreservation:
    """Sampled (temperature > 0) outputs keep the target distribution."""

    def test_oracle_drafter_reproduces_seeded_sequence(self, tiny_model):
        """Deterministic distribution-preservation: run a seeded temp>0
        request spec-off, then spec-on with an oracle drafter proposing
        exactly that sequence.  Every draft is accepted, so the verify
        call's per-position draws must equal the plain path's draws
        bit-for-bit — which pins (a) logits parity at drafted positions,
        (b) identical sampler invocation (penalties/tiers), and (c) the
        sequential key-split stream.  Any of those breaking would change
        the sampled distribution; none may."""
        cfg, params = tiny_model
        k = 3
        # max_tokens = 1 + m*(k+1): every spec round drafts exactly k
        # (the budget clamp never shortens a draft, which would desync
        # the key stream via the fixed-width sampling scan)
        sp = SamplingParams(
            temperature=0.9, top_p=0.95, max_tokens=1 + 4 * (k + 1),
            seed=777,
        )
        off = make_engine(cfg, params, False, spec_tokens=k)
        base = off.generate([REP], sp)[0]
        assert len(base) == sp.max_tokens

        on = make_engine(cfg, params, True, spec_tokens=k)
        target = list(REP) + list(base)

        class Oracle:
            def draft(self, req_id, tokens, cap):
                nxt = target[len(tokens): len(tokens) + cap]
                # only propose full-width drafts so the fixed-width
                # verify scan splits keys exactly like plain decode
                return nxt if len(nxt) == cap else []

            def observe(self, *a):
                pass

            def forget(self, *a):
                pass

            def disabled_count(self):
                return 0

        on.spec = Oracle()
        got = on.generate([REP], sp)[0]
        assert got == base
        assert on.num_spec_steps >= 4   # the spec path carried the run

    @pytest.mark.slow   # ~1.5k engine requests per mode
    def test_sampled_marginals_match(self, tiny_model):
        """Statistical acceptance (the test_sampling_exact harness style,
        TV over pooled output histograms): the marginal distribution of
        generated tokens is unchanged by speculation.  Every emitted
        token is a true target-distribution draw — position 0 of each
        verify unconditionally, later positions as accept-or-emit
        rejection sampling — so the pooled histograms must agree up to
        sampling noise."""
        cfg, params = tiny_model
        # many distinct tokens so 1-gram draft hits are common at temp>0
        # (100 tokens: fits the 128-token page capacity with gen room)
        prompt = list(range(40, 90)) * 2
        sp = SamplingParams(temperature=0.7, max_tokens=5)
        N = 384

        def histogram(spec, rng_seed):
            eng = make_engine(
                cfg, params, spec, max_decode_batch=8, num_pages=512,
                max_prefill_len=256, rng_seed=rng_seed,
            )
            counts = np.zeros(cfg.vocab_size, np.int64)
            drafted = 0
            for wave in range(0, N, 8):
                reqs = [
                    Request(
                        id=f"d{spec}-{rng_seed}-{wave + i}",
                        prompt_tokens=list(prompt),
                        sampling=sp,
                    )
                    for i in range(8)
                ]
                for r in reqs:
                    eng.add_request(r)
                while eng.has_work():
                    eng.step()
                for r in reqs:
                    # skip output[0]: prefill-sampled, identical code
                    # path both modes — pool only decode-path tokens
                    counts += np.bincount(
                        r.output_tokens[1:], minlength=cfg.vocab_size
                    )
                drafted = getattr(eng, "num_spec_drafted_tokens", 0)
            return counts / counts.sum(), drafted

        # self-calibrating threshold: the null TV between two spec-OFF
        # runs with different engine RNG streams measures the pure
        # sampling noise at this sample size/support — the spec-on TV
        # must sit in the same band, not a hand-picked absolute
        off_a, _ = histogram(False, rng_seed=0)
        off_b, _ = histogram(False, rng_seed=1)
        on, drafted = histogram(True, rng_seed=2)
        assert drafted > 50, "spec path never engaged — vacuous test"
        tv_null = 0.5 * float(np.abs(off_a - off_b).sum())
        tv_on = 0.5 * float(np.abs(off_a - on).sum())
        assert tv_on < max(2.0 * tv_null, 0.05), (
            f"spec-on marginals drifted: TV={tv_on:.4f} vs "
            f"null TV={tv_null:.4f}"
        )


class TestWorstCaseDegradation:
    def test_adversarial_drafter_costs_no_extra_steps(
        self, tiny_model, monkeypatch
    ):
        """Zero-acceptance drafting: outputs stay bit-identical, the
        device-step count stays EQUAL to spec-off (every verify call
        still emits its bonus token), and the acceptance EMA benches the
        slot after a handful of misses — the throughput-within-10%
        acceptance criterion, asserted on step counts rather than
        wall-clock."""
        cfg, params = tiny_model
        # always propose a token stream the greedy model will not emit
        # (xor flips the low bit of the trailing token): n-gram state,
        # EMA, cooldown all run the REAL SpecDecoder logic
        monkeypatch.setattr(
            "helix_tpu.engine.spec.propose",
            lambda tokens, k, **kw: [(int(tokens[-1]) ^ 1) % 256] * k,
        )
        sp = SamplingParams(temperature=0.0, max_tokens=32)

        def run(spec):
            eng = make_engine(cfg, params, spec)
            req = Request(
                id="adv", prompt_tokens=list(REP), sampling=sp
            )
            eng.add_request(req)
            peak_disabled = 0
            while eng.has_work():
                eng.step()
                # request teardown forgets drafting state, so the EMA
                # bench is only observable mid-run
                peak_disabled = max(
                    peak_disabled, eng.spec_disabled_slots()
                )
            return req.output_tokens, eng, peak_disabled

        base, eng_off, _ = run(False)
        spec, eng_on, peak_disabled = run(True)
        assert spec == base
        # EMA floor: 0.65^t < 0.12 at t=5 -> at most ~6 verify calls
        # before the slot is benched for reprobe_after opportunities
        assert 1 <= eng_on.num_spec_steps <= 6
        assert eng_on.num_spec_accepted_tokens == 0
        assert peak_disabled == 1
        # zero-acceptance verify still emits 1 token/slot/call: the
        # adversary cannot inflate the device-step count at all
        assert (
            eng_on.num_decode_device_steps
            == eng_off.num_decode_device_steps
        )


@pytest.mark.slow
class TestCompositionParity:
    """Spec x int8 KV x chunked/mixed prefill x fused windows, greedy
    parity — every engine feature the verify path must compose with, in
    one run (each axis keeps a faster tier-1 sibling)."""

    def test_int8_kv_and_mixed_step_parity(self, tiny_model):
        cfg, params = tiny_model
        long_prompt = (REP * 3)[:60]   # > max_prefill_len: chunks + mixed
        prompts = [REP, long_prompt, MIX]
        sp = SamplingParams(temperature=0.0, max_tokens=20)

        def run(spec):
            eng = make_engine(
                cfg, params, spec, kv_cache_dtype="int8",
                enable_mixed_step=True, max_prefill_len=16,
                decode_steps_per_sync=4, adaptive_sync_max_streams=0,
            )
            out = eng.generate(prompts, sp)
            return out, eng

        base, _ = run(False)
        spec, eng_on = run(True)
        assert spec == base
        assert eng_on.num_spec_accepted_tokens > 0
        assert eng_on.num_mixed_steps > 0   # chunked admission ran mixed

    def test_unsupported_families_fall_back(self, tiny_model):
        """MoE configs log and run plain decode (engine.spec is None)."""
        cfg, _ = tiny_model
        moe_cfg = ModelConfig.tiny(
            dtype="float32", num_experts=4, num_experts_per_tok=2
        )
        params = init_params(moe_cfg, jax.random.PRNGKey(7),
                             dtype=jnp.float32)
        eng = make_engine(moe_cfg, params, True)
        assert eng.spec is None
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        out = eng.generate([MIX], sp)
        assert len(out[0]) == 8
        assert eng.num_spec_steps == 0
