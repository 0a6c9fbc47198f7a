"""The benchmark's corpus generator: exact record counts, and the shape
its configuration states."""
import json
import os

import numpy as np
import pytest

from _benchroot import REPO

from bench.harness import corpus


def _config(name="registry"):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_exact_record_count_for_every_seed(seed):
    cfg = _config()
    columns, entity = corpus.generate(cfg["generator"], cfg["records"],
                                      [seed, 0])
    assert len(entity) == cfg["records"] == 131_072
    assert set(columns) == set(cfg["generator"]["fields"])
    for tokens, mask in columns.values():
        assert tokens.shape[0] == mask.shape[0] == cfg["records"]
        assert tokens.dtype == np.uint32 and mask.dtype == bool


def test_same_seed_same_corpus():
    gen = _config()["generator"]
    a, ea = corpus.generate(gen, 5000, [3, 0])
    b, eb = corpus.generate(gen, 5000, [3, 0])
    np.testing.assert_array_equal(ea, eb)
    for k in a:
        np.testing.assert_array_equal(a[k][0], b[k][0])
        np.testing.assert_array_equal(a[k][1], b[k][1])


def _rows(columns):
    """The records as sorted row tuples (order-free)."""
    parts = [np.concatenate([t.astype(np.int64), m.astype(np.int64)], axis=1)
             for _, (t, m) in sorted(columns.items())]
    rows = np.concatenate(parts, axis=1)
    return rows[np.lexsort(rows.T[::-1])]


def test_content_seed_fixes_the_records_and_seed_orders_them():
    cfg = dict(_config(), records=3000)
    a, ea = corpus.records(cfg, 1)
    b, eb = corpus.records(cfg, 2**35 + 1)
    np.testing.assert_array_equal(_rows(a), _rows(b))
    assert not np.array_equal(a["surname"][0], b["surname"][0])
    np.testing.assert_array_equal(np.sort(ea), np.sort(eb))


def _bounded_zipf_p(card, a):
    w = np.arange(1, card + 1, dtype=np.float64) ** -a
    return w / w.sum()


@pytest.mark.parametrize("seed", [5, 2**36 + 9])
def test_distributions_follow_the_configuration(seed):
    gen = _config()["generator"]
    n = 60_000
    columns, entity = corpus.generate(gen, n, [seed, 0])
    _, sizes = np.unique(entity, return_counts=True)
    # data set 3's proportions: originals are a fixed share of the records
    assert len(sizes) == round(n * gen["originals_share"])
    assert sizes.max() <= 1 + gen["max_dups"]
    # every original but the last drawn holds its full bounded-Zipf count;
    # clusters of 2..6 records follow that law among originals that have
    # duplicates (the one truncated original aside)
    dup = np.bincount(sizes - 1, minlength=gen["max_dups"] + 1)[1:]
    want = _bounded_zipf_p(gen["max_dups"], gen["dups_zipf_a"])
    np.testing.assert_allclose(dup / dup.sum(), want, atol=0.02)
    for name, f in gen["fields"].items():
        tok, mask = columns[name]
        lo, hi = f["tokens"]
        assert tok.shape[1] == hi
        held = mask.sum(axis=1)
        assert set(np.unique(held)) <= {0} | set(range(lo, hi + 1))
        # duplicates blank a field now and then: presence falls a little
        # below the originals' share, never above it
        present = (held > 0).mean()
        assert f["present"] - 0.08 < present <= f["present"] + 0.01, name


def test_originals_follow_the_field_laws():
    gen = _config()["generator"]
    rng = np.random.default_rng(11)
    got = corpus.originals(rng, gen, 40_000)
    state = gen["fields"]["state"]
    tok, mask = got["state"]
    ids = {t: i for i, t in enumerate(corpus.token_hash(
        np.arange(state["card"]), sorted(gen["fields"]).index("state") + 1))}
    seen = np.array([ids[t] for t in tok[mask[:, 0], 0]])
    share = np.bincount(seen, minlength=state["card"]) / len(seen)
    np.testing.assert_allclose(
        share, _bounded_zipf_p(state["card"], state["zipf_a"]), atol=0.01)
    lo, hi = gen["fields"]["address_1"]["tokens"]
    held = got["address_1"][1].sum(axis=1)
    assert set(np.unique(held[held > 0])) == set(range(lo, hi + 1))


def test_a_duplicate_modifies_one_to_max_fields():
    gen = _config()["generator"]
    rng = np.random.default_rng(12)
    base = corpus.originals(rng, gen, 20_000)
    dup = corpus.modify(rng, gen, base)
    changed = np.zeros(20_000, np.int64)
    for name in base:
        t0, m0 = base[name]
        t1, m1 = dup[name]
        changed += np.any((m0 != m1) | (m0 & (t0 != t1)), axis=1)
    # a field that was missing stays as it is when it is picked
    assert changed.max() <= gen["max_modified_fields"]
    assert (changed >= 1).mean() > 0.95
