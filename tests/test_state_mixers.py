"""A layer kind with a per-sequence state is ONE record in
``models/mixers.py``: nothing above ``models/`` spells a kind's name, and
every record holds all the modules above ask of a kind."""

import dataclasses
import importlib.util
import os
import re
import tokenize

import numpy as np
import pytest

from helix_tpu.models.mixers import STATE_MIXERS, Series, flight_fields

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ABOVE_MODELS = (
    "helix_tpu/engine/engine.py", "helix_tpu/engine/kv_cache.py",
    "helix_tpu/engine/ragged.py", "helix_tpu/serving/engine_loop.py",
    "helix_tpu/serving/openai_api.py", "helix_tpu/obs/flight.py",
)


def _code_tokens(path):
    """``(type, string, line)`` of a module's tokens, comments and
    docstrings left out (a docstring: a string that is a whole statement)."""
    with open(path, "rb") as f:
        toks = list(tokenize.tokenize(f.readline))
    skip = (tokenize.COMMENT, tokenize.NL, tokenize.NEWLINE, tokenize.INDENT,
            tokenize.DEDENT, tokenize.ENCODING)
    out, fresh = [], True       # fresh: at the start of a statement
    for i, t in enumerate(toks):
        if t.type in skip:
            fresh = fresh or t.type in (
                tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT)
            continue
        if (t.type == tokenize.STRING and fresh
                and toks[i + 1].type in (tokenize.NEWLINE, tokenize.NL)):
            continue
        fresh = False
        out.append((t.type, t.string, t.start[0]))
    return out


def names_of_a_kind(root: str, kinds) -> list:
    """Every place a module above ``models/`` names a state kind, by the
    forms the modules named one before a kind was a record."""
    alt = "|".join(map(re.escape, kinds))
    ident = re.compile(
        rf"^(num_({alt})_\w*|\w*?_?({alt})_(fn|layers|chunks|rows\w*)"
        r"|window_ring\w*|sliding_window)$")
    found = []
    for rel in ABOVE_MODELS:
        toks = _code_tokens(os.path.join(root, rel))
        for i, (typ, text, line) in enumerate(toks):
            if typ == tokenize.NAME and ident.match(text):
                found.append(f"{rel}:{line}: {text}")
            if typ != tokenize.STRING:
                continue
            body = re.sub(r"""^[A-Za-z]*("{3}|'{3}|"|')|("{3}|'{3}|"|')$""",
                          "", text)
            # (an attribute read by name, ``getattr(eng, "num_x_rows")``, is
            # an attribute)
            if (body in kinds or re.search(rf"helix_({alt})_", body)
                    or ident.match(body)):
                found.append(f"{rel}:{line}: {text}")
            near = [s for _, s, _ in toks[max(i - 3, 0):i + 4]]
            if "state_mixer" in near and {"==", "!=", "in"} & set(near):
                found.append(f"{rel}:{line}: state_mixer against {text}")
    return found


def test_no_module_above_models_names_a_state_kind():
    assert names_of_a_kind(ROOT, tuple(STATE_MIXERS)) == []


def test_the_fifth_kind_is_spelt_nowhere_above_models():
    """PR 45's kind (Mamba-2) came as a record, its compute and its operator:
    no module under ``engine/``, ``serving/`` or ``obs/`` names it, by its
    kind's name, its operator's (``ssd``: the series, the launch attributes,
    the flight fields) or its family's."""
    assert "mamba2" in STATE_MIXERS
    assert names_of_a_kind(ROOT, ("mamba2", "mamba", "ssd")) == []
    for rel in ABOVE_MODELS:
        with open(os.path.join(ROOT, rel)) as f:
            text = f.read().lower()
        assert "mamba" not in text and "ssd_" not in text, rel


def test_the_search_finds_the_names_it_is_for(tmp_path):
    """The forms are found where they stand: the search is not blind."""
    for rel in ABOVE_MODELS:
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text('"""a docstring may say conv_layers"""\n')
    (tmp_path / ABOVE_MODELS[0]).write_text(
        '"""window_fn in a docstring"""\n'
        "# num_conv_layers in a comment\n"
        "burn_windows = _decode_window = 0\n"
        "a = cfg.num_conv_layers\n"
        "b = dict(retention_fn=1)\n"
        'c = "helix_deltanet_chunks_total"\n'
        'd = cfg.state_mixer == "window"\n'
        "e = eng.window_ring_bytes_read + m.sliding_window\n"
        "f = self._note_window_rows(plan)\n"
        'g = getattr(eng, "num_deltanet_chunks", 0)\n')
    found = names_of_a_kind(str(tmp_path), tuple(STATE_MIXERS))
    assert [f.split(": ", 1)[1] for f in found] == [
        "num_conv_layers", "retention_fn", '"helix_deltanet_chunks_total"',
        '"window"', 'state_mixer against "window"', "window_ring_bytes_read",
        "sliding_window", "_note_window_rows", '"num_deltanet_chunks"']


KINDS = sorted(STATE_MIXERS)
NO_POS = np.zeros((0,), np.int64)


def _counts(m, cfg, cache_cfg) -> dict:
    """The kind's counts at zero: its account of an empty launch."""
    return m.account(cfg, cache_cfg, (), NO_POS, 0) if m.account else {}


def _gauges(m, cfg) -> dict:
    return m.gauges(cfg, NO_POS) if m.gauges else {}


@pytest.mark.parametrize("kind", KINDS)
def test_a_record_is_complete(kind):
    m = STATE_MIXERS[kind]
    optional = ("check_geometry", "account", "gauges", "window")
    for f in dataclasses.fields(m):
        assert f.name in optional or getattr(m, f.name) is not None, f.name
    assert m.refused_as and m.call_refusal and m.token_args >= 1
    for c in (m.arrays, m.rows_fn, m.oracle,
              *filter(None, (getattr(m, name) for name in optional))):
        assert callable(c)
    # a series reads a count, the layers or the pool's bytes (any thread may
    # render it); a launch attribute or a flight field also a level
    cfg, cache_cfg = _cfgs(kind)
    keys = set(_counts(m, cfg, cache_cfg)) | {"layers", "pool_bytes"}
    assert {s.value for s in m.series} <= keys
    assert {k for _, k in m.launch + m.flight} <= keys | set(_gauges(m, cfg))
    assert all(isinstance(s, Series) and s.kind in ("counter", "gauge")
               for s in m.series)


@pytest.mark.parametrize("kind", KINDS)
def test_refusal_rows_name_the_seven_settings_or_say_which_it_serves(kind):
    m = STATE_MIXERS[kind]
    from helix_tpu.engine.engine import _SETTINGS

    refused = [setting for setting, _ in m.refusals]
    assert len(_SETTINGS) == 7 and len(set(refused)) == len(refused)
    serves = set(_SETTINGS) - set(refused)
    assert set(refused) <= set(_SETTINGS)
    assert all(why and why[0].islower() for _, why in m.refusals)
    # the one setting a kind is served with: the prefix cache, by the kind
    # whose steps hand back boundary states for it
    assert serves == ({"prefix_cache"} if m.snapshots else set())


def test_the_refusal_rows_stand_in_the_order_they_were_written():
    """The first row met is the one raised, and the engine splices the
    kinds' rows among its own by the records' ORDER: the 42 rows as
    (setting, what of the model meets it), in the order they have had since
    each was written."""
    from helix_tpu.engine.engine import _REFUSALS, _SETTINGS

    seven = list(_SETTINGS)
    assert seven == ["multi_device", "int8_kv", "adapters", "spec_decode",
                     "tiered", "host_tier", "prefix_cache"]
    want = (
        [(s, "latent attention (MLA)") for s in seven[:5]]
        + [(s, "recurrent state (gated short convolutions)")
           for s in seven[:6]]
        + [("int8_kv", "kv heads packed into one lane tile (head width "
            "under 128)")]
        + [(s, "a matrix state (power retention)") for s in seven]
        + [(s, "a matrix state and a conv tail (gated delta rule)")
           for s in seven]
        + [("multi_device", "held experts (one expert-parallel rank of the "
            "routed experts)")]
        + [(s, "a ring of K/V a slot (sliding-window attention)")
           for s in seven]
        + [(s, "a state-space state and a conv tail (Mamba-2)")
           for s in seven]
        + [("host_tier", "a sparse-attention indexer (an index-key pool "
            "beside the latent pool)")])
    assert [(key, prop) for key, (prop, _), _ in _REFUSALS] == want
    assert len(want) == 42


def _cfgs(kind):
    """A tiny model with one layer of the kind, and an engine's cache
    configuration for it."""
    from helix_tpu.engine.engine import EngineConfig
    from helix_tpu.models.common import ModelConfig

    cfg = ModelConfig.tiny(
        vocab_size=64, dtype="float32", num_layers=2,
        layer_types=(kind, "attn"), sliding_window=8, conv_kernel=4,
        mamba_heads=4, mamba_head_dim=32, mamba_groups=1, mamba_state_size=8)
    return cfg, EngineConfig(max_decode_batch=2).cache_config("float32")


@pytest.mark.parametrize("kind", KINDS)
def test_the_account_of_an_empty_launch_is_all_zeros(kind):
    m = STATE_MIXERS[kind]
    cfg, cache_cfg = _cfgs(kind)
    assert cfg.state_kind is m and cfg.num_state_layers == 1
    counts = _counts(m, cfg, cache_cfg)
    # every count at every launch, whatever it holds: the engine's mapping
    # starts as this
    assert counts == dict.fromkeys(
        m.account(cfg, cache_cfg, (), NO_POS, 3) if m.account else (), 0)
    assert all(isinstance(n, int) for n in counts.values())
    # and the flight record shows every kind's fields, this kind's alone
    # from the engine's values
    values = {**dict.fromkeys(counts, 5), "layers": 1, "pool_bytes": 9,
              **_gauges(m, cfg)}
    fields = flight_fields(m, values, dict.fromkeys(counts, 2))
    assert set(fields) == {
        f for k in STATE_MIXERS.values() for f, _ in k.flight}
    for field, key in m.flight:
        assert fields[field] == (3 if key in counts else values[key])
    assert all(v == 0 for f, v in fields.items()
               if f not in dict(m.flight))
    assert set(flight_fields(None, {}, {}).values()) == {0}


@pytest.mark.parametrize("kind", KINDS)
def test_series_names_pass_the_linters_naming_contract(kind):
    spec = importlib.util.spec_from_file_location(
        "lint_metrics", os.path.join(ROOT, "tools", "lint_metrics.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    m = STATE_MIXERS[kind]
    assert m.series
    for s in m.series:
        assert lint.NAME_RE.fullmatch(s.name), s.name
        assert not s.name.endswith(lint._BAD_SUFFIXES), s.name
        assert not s.name.endswith(lint._RESERVED_SUFFIXES), s.name
        assert s.name.endswith("_total") == (s.kind == "counter"), s.name


# ---- a kind's fused window is nothing to a model without one ---------------
#
# sha256 of ``fn.lower(*args).as_text()`` (it carries no locations; taken
# under this suite's ``conftest.py``, whose matmul precision is in the text)
# of a tiny dense model's three programs with a fused tail, AT THE COMMIT
# BEFORE a state kind could keep a window's tokens beside its pool (d23911d,
# PR 46).  The same digests came out of that tree and of this one for a tiny
# model of every other kind too (latent, conv, deltanet, window, mamba2: 18
# programs; PERF.md section 6, PR 47).  A PR that MEANS to change the step
# every model runs takes new digests from its own tree and says so.
_DENSE_PROGRAMS = {
    "decode": ((0, 0, False), "c978b3b4185b112051c29602ca1e7907"
                              "98c748c70c21d5caf53006376487e687"),
    "wave": ((16, 2, False), "52d7205379a8feb72fa06534ee9eb445"
                             "57a2bd62597222c7b145432d318a2904"),
    "chunk_with_history": ((16, 1, True), "62ee93659baf2fcc4ed0898d3824b769"
                                          "dec03bd61430ac26b183406178fda9c8"),
}


@pytest.mark.parametrize("program", sorted(_DENSE_PROGRAMS))
def test_a_model_without_a_state_kind_lowers_to_the_text_it_had(program):
    import hashlib

    import jax

    import joint_pass
    from helix_tpu.engine.engine import Engine, EngineConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    assert cfg.state_kind is None
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(3)), EngineConfig(
        max_decode_batch=3, page_size=8, num_pages=96, max_pages_per_seq=16,
        max_prefill_len=16, attn_backend="reference",
        decode_steps_per_sync=4))
    shape, digest = _DENSE_PROGRAMS[program]
    fn, args = joint_pass.step_program(eng, *shape)
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_only_a_kind_with_a_window_is_told_the_step_of_the_tail():
    """A kind that keeps nothing beside its pool (``window`` None) gets no
    pending tokens in its carry; the one that does starts them empty."""
    from helix_tpu.models.common import BRUMBY_14B

    kinds = {name for name, m in STATE_MIXERS.items() if m.window is not None}
    assert kinds == {"retention", "mamba2"}
    k, v, lg, seen = STATE_MIXERS["retention"].window(
        dataclasses.replace(BRUMBY_14B, num_layers=2,
                            layer_types=("retention",) * 2), 3, 8)
    assert k.shape == v.shape == (2, 3, 8, 8, 128) and k.dtype == np.float32
    assert lg.shape == (2, 3, 8, 8) and seen.shape == (2, 3)
    # Mamba-2's: ``dt x`` in the pool's packed rows, ``B`` a group, ``dt A``
    cfg, _ = _cfgs("mamba2")
    x, B, la, seen = STATE_MIXERS["mamba2"].window(cfg, 3, 8)
    assert x.shape == (1, 3, 8, 1, 128) and B.shape == (1, 3, 1, 8, 8)
    assert la.shape == (1, 3, 8, 4) and seen.shape == (1, 3)
    assert {a.dtype for a in (x, B, la)} == {np.dtype("float32")}
    assert not any(np.any(np.asarray(a)) for a in (k, v, lg, seen))
