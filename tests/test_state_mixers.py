"""A layer kind with a per-sequence state, and a kind of page, is ONE record
in ``models/mixers.py``: nothing above ``models/`` spells a kind's name or
reads the ``ModelConfig`` keys that tell one from another, and every record
holds all the modules above ask of a kind."""

import dataclasses
import importlib.util
import os
import re
import tokenize

import numpy as np
import pytest

from helix_tpu.models.mixers import (
    PAGE_KINDS, STATE_MIXERS, PageKind, Pool, Series, flight_fields,
)

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


# what the modules above ``models/`` told a kind of page by, before a kind of
# page was a record: the operators' names (in series, attributes and counts)
# and the ``ModelConfig`` keys that are one kind's alone
PAGE_OPERATORS = ("mla", "dsa")
PAGE_NAMES = ("is_mla", "is_dsa", "kv_lora_rank", "index_heads", "index_topk",
              "index_head_dim", "_note_dsa", "dsa_counts", "latent_widths")


def names_of_a_kind(root: str, kinds, names=()) -> list:
    """Every place a module above ``models/`` names a kind, by the forms the
    modules named one before a kind was a record (and by ``names``, whole)."""
    alt = "|".join(map(re.escape, kinds))
    ident = re.compile(
        rf"^(num_({alt})_\w*|\w*?_?({alt})_(fn|layers|chunks|rows\w*)"
        r"|window_ring\w*|sliding_window)$")
    found = []
    for rel in ABOVE_MODELS:
        toks = _code_tokens(os.path.join(root, rel))
        for i, (typ, text, line) in enumerate(toks):
            if typ == tokenize.NAME and (ident.match(text) or text in names):
                found.append(f"{rel}:{line}: {text}")
            if typ != tokenize.STRING:
                continue
            body = re.sub(r"""^[A-Za-z]*("{3}|'{3}|"|')|("{3}|'{3}|"|')$""",
                          "", text)
            # (an attribute read by name, ``getattr(eng, "num_x_rows")``, is
            # an attribute)
            if (body in kinds or re.search(rf"helix_({alt})_", body)
                    or ident.match(body) or body in names):
                found.append(f"{rel}:{line}: {text}")
            near = [s for _, s, _ in toks[max(i - 3, 0):i + 4]]
            if "state_mixer" in near and {"==", "!=", "in"} & set(near):
                found.append(f"{rel}:{line}: state_mixer against {text}")
    return found


@pytest.mark.parametrize("kinds, names", [
    (tuple(STATE_MIXERS), ()), (PAGE_OPERATORS, PAGE_NAMES)],
    ids=["state", "page"])
def test_no_module_above_models_names_a_state_kind(kinds, names):
    assert names_of_a_kind(ROOT, kinds, names) == []


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
        'g = getattr(eng, "num_deltanet_chunks", 0)\n'
        "h = 3 + cfg.is_dsa if cfg.is_mla else cfg.kv_lora_rank\n"
        'i = ("helix_dsa_rows_total", eng.num_mla_page_fetches)\n'
        'j = getattr(eng, "dsa_counts", {}) or self._note_dsa(plan)\n'
        "k = a_latent_page + the_index_pool  # neither is a name\n")
    found = names_of_a_kind(str(tmp_path), tuple(STATE_MIXERS))
    assert [f.split(": ", 1)[1] for f in found] == [
        "num_conv_layers", "retention_fn", '"helix_deltanet_chunks_total"',
        '"window"', 'state_mixer against "window"', "window_ring_bytes_read",
        "sliding_window", "_note_window_rows", '"num_deltanet_chunks"']
    # ... and the page kinds' names, beside the forms every search looks for
    paged = names_of_a_kind(str(tmp_path), PAGE_OPERATORS, PAGE_NAMES)
    assert [f.split(": ", 1)[1] for f in paged if f not in found] == [
        "is_dsa", "is_mla", "kv_lora_rank", '"helix_dsa_rows_total"',
        "num_mla_page_fetches", '"dsa_counts"', "_note_dsa"]


RECORDS = {**STATE_MIXERS, **PAGE_KINDS}
KINDS = sorted(RECORDS)
NO_POS = np.zeros((0,), np.int64)
assert len(RECORDS) == len(STATE_MIXERS) + len(PAGE_KINDS)


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


step_text = _tool("step_text")


def _counts(m, cfg, cache_cfg) -> dict:
    """The kind's counts at zero: its account of an empty launch."""
    return m.account(cfg, cache_cfg, (), NO_POS, 0) if m.account else {}


def _gauges(m, cfg) -> dict:
    return m.gauges(cfg, NO_POS) if m.gauges else {}


@pytest.mark.parametrize("kind", KINDS)
def test_a_record_is_complete(kind):
    m = RECORDS[kind]
    optional = ("check_geometry", "account", "gauges", "window", "base") + (
        ("call_refusal",) if isinstance(m, PageKind) else ())
    for f in dataclasses.fields(m):
        assert f.name in optional or getattr(m, f.name) is not None, f.name
    cfg, cache_cfg = _cfgs(kind)
    keys = set(_counts(m, cfg, cache_cfg)) | {"layers", "pool_bytes"}
    if isinstance(m, PageKind):
        assert m.refused_as and m.token_args >= 3
        assert m.base is None or m.base in PAGE_KINDS.values()
        called = (m.pools, m.geometry, m.token_arrays, m.attend,
                  m.query_block, m.check_geometry, m.account)
        # what ``k_pages`` and, where there is one, ``v_pages`` hold
        pools = m.pools(cfg, cache_cfg.page_size)
        assert 1 <= len(pools) <= 2 and all(
            isinstance(p, Pool) and p.page[0] == cache_cfg.page_size
            for p in pools)
        assert len(m.token_arrays(cfg)) == 2 and len(m.geometry(cfg)) == 2
        keys |= {p.holds + "_pool_bytes" for p in pools}
    else:
        assert m.refused_as and m.call_refusal and m.token_args >= 1
        called = (m.arrays, m.rows_fn, m.oracle)
    for c in (*called,
              *filter(None, (getattr(m, name, None) for name in optional[:4]))):
        assert callable(c)
    # a series reads a count, the layers or a pool's bytes (any thread may
    # render it); a launch attribute or a flight field also a level
    assert {s.value for s in m.series} <= keys
    assert {k for _, k in m.launch + m.flight} <= keys | set(_gauges(m, cfg))
    assert all(isinstance(s, Series) and s.kind in ("counter", "gauge")
               for s in m.series)


@pytest.mark.parametrize("kind", KINDS)
def test_refusal_rows_name_the_seven_settings_or_say_which_it_serves(kind):
    m = RECORDS[kind]
    from helix_tpu.engine.engine import _SETTINGS

    refused = [setting for setting, _ in m.refusals]
    assert len(_SETTINGS) == 7 and len(set(refused)) == len(refused)
    serves = set(_SETTINGS) - set(refused)
    assert set(refused) <= set(_SETTINGS)
    assert all(why and why[0].islower() for _, why in m.refusals)
    if isinstance(m, PageKind):
        # pages are shared by their ids: every kind of page is served with
        # the prefix cache, and K/V pages with every setting
        assert "prefix_cache" in serves
        assert (serves == set(_SETTINGS)) == (kind == "kv")
        return
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
    """A tiny model with one layer of the state kind beside one with K/V
    pages, or with two layers of the kind of page, and an engine's cache
    configuration for it."""
    from helix_tpu.engine.engine import EngineConfig
    from helix_tpu.models.common import ModelConfig

    if kind in PAGE_KINDS:
        # (the tiny models ``tools/step_text.py`` lowers: their names are the
        # page kinds')
        cfg = ModelConfig.tiny(
            vocab_size=64, dtype="float32", **step_text.MODELS[kind])
        return cfg, EngineConfig(max_decode_batch=2).cache_config("float32")
    cfg = ModelConfig.tiny(
        vocab_size=64, dtype="float32", num_layers=2,
        layer_types=(kind, "attn"), sliding_window=8, conv_kernel=4,
        mamba_heads=4, mamba_head_dim=32, mamba_groups=1, mamba_state_size=8)
    return cfg, EngineConfig(max_decode_batch=2).cache_config("float32")


# the one count a launch adds to whatever its rows hold: the gather out of
# the index-key pool moves EVERY slot's page table at its whole width
# (``mixers._indexed_account``); the engine's mapping starts at zero all the
# same
_MOVED_WHATEVER_THE_ROWS = {"latent_indexed": {"index_bytes_read"}}


@pytest.mark.parametrize("kind", KINDS)
def test_the_account_of_an_empty_launch_is_all_zeros(kind):
    m = RECORDS[kind]
    cfg, cache_cfg = _cfgs(kind)
    if isinstance(m, PageKind):
        assert cfg.page_kind is m and cfg.num_attn_layers == 2
    else:
        assert cfg.state_kind is m and cfg.num_state_layers == 1
        assert cfg.page_kind is PAGE_KINDS["kv"]
    counts = _counts(m, cfg, cache_cfg)
    # every count at every launch, whatever it holds: the engine's mapping
    # starts as this
    assert set(counts) == set(
        m.account(cfg, cache_cfg, (), NO_POS, 3) if m.account else ())
    moved = _MOVED_WHATEVER_THE_ROWS.get(kind, set())
    assert {k for k, n in counts.items() if n} == moved
    assert all(isinstance(n, int) for n in counts.values())
    # and the flight record shows every kind's fields, this kind's alone
    # from the engine's values
    values = {**dict.fromkeys(counts, 5), "layers": 1, "pool_bytes": 9,
              **_gauges(m, cfg)}
    fields = flight_fields((m,), values, dict.fromkeys(counts, 2))
    assert set(fields) == {
        f for k in RECORDS.values() for f, _ in k.flight}
    for field, key in m.flight:
        assert fields[field] == (3 if key in counts else values[key])
    assert all(v == 0 for f, v in fields.items()
               if f not in dict(m.flight))
    assert set(flight_fields((), {}, {}).values()) == {0}


@pytest.mark.parametrize("kind", KINDS)
def test_series_names_pass_the_linters_naming_contract(kind):
    lint = _tool("lint_metrics")
    m = RECORDS[kind]
    assert m.series
    for s in m.series:
        assert lint.NAME_RE.fullmatch(s.name), s.name
        assert not s.name.endswith(lint._BAD_SUFFIXES), s.name
        assert not s.name.endswith(lint._RESERVED_SUFFIXES), s.name
        assert s.name.endswith("_total") == (s.kind == "counter"), s.name


# ---- code that moves leaves the step programs as they were ------------------
#
# sha256 of ``fn.lower(*args).as_text()`` (it carries no locations; taken
# under this suite's ``conftest.py``, whose matmul precision is in the text)
# of a tiny model's three programs with a fused tail, as
# ``tools/step_text.py`` prints them (its ``MODELS``: a dense model, a latent
# one, a latent one behind an indexer, delta-rule layers beside K/V pages).
# The dense model's are of THE COMMIT BEFORE a state kind could keep a
# window's tokens beside its pool (d23911d, PR 46), and came out of every tree
# since; the others' of the commit before a kind of page was a record
# (2b045e4, PR 56), with the same from that tree and from PR 57's for every
# model the tool has (30 programs: PERF.md section 6, PRs 47 and 57).  A PR
# that MEANS to change the step a model runs takes new digests from its own
# tree and says so.
_PROGRAMS = {
    ("kv", "decode"): "c978b3b4185b112051c29602ca1e7907"
                      "98c748c70c21d5caf53006376487e687",
    ("kv", "wave"): "52d7205379a8feb72fa06534ee9eb445"
                    "57a2bd62597222c7b145432d318a2904",
    ("kv", "chunk_with_history"): "62ee93659baf2fcc4ed0898d3824b769"
                                  "dec03bd61430ac26b183406178fda9c8",
    ("latent", "decode"): "7175cc87e60fa381cd9cf3679a7f2b5c"
                          "ab6d38d88a068bf217b72b2737a6fa60",
    ("latent", "wave"): "2e315e12ddd1395296818096926fbf61"
                        "9a579af31cef30dce8f286edc2575434",
    ("latent", "chunk_with_history"): "e2b5747de313baa0182d5e59845c0173"
                                      "fb8dcffca2c123375f61cb935ada6bb3",
    ("latent_indexed", "decode"): "8ca80bb89fa3d63def47aad20c289462"
                                  "7adf9bbe1d35750b1a33246aad44dd4d",
    ("latent_indexed", "wave"): "2ab5a1239ac052686ce294efee59da6c"
                                "550b040fb638917241c8792f8f94e409",
    ("latent_indexed", "chunk_with_history"):
        "6e1a1a7fee40052e231dad6a77454327305a6b76c0c71177ec34e2080aa91ee8",
    ("deltanet", "decode"): "7dfedda505aa999a2a25d6a08c1e8d18"
                            "49dfcf12d6bb4d6f30abd93009a9f370",
    ("deltanet", "wave"): "917de55c4144dd37b58cf5cde74d09d3"
                          "7a38efeffab4b0dcc6511815cb124f82",
    ("deltanet", "chunk_with_history"): "fa9d5e71fc9621e9733d400e326ab6c3"
                                        "96d4012108ee9c7e153bd327bf0d20a5",
}


@pytest.mark.parametrize("model, program", sorted(_PROGRAMS))
def test_a_model_without_a_state_kind_lowers_to_the_text_it_had(
        model, program):
    assert step_text.digest(model, program) == _PROGRAMS[model, program]


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
