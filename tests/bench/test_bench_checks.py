"""Each cell's check fails its control and the faults the cell can have.

The control is the plain reference put in the program's place with one
stated guarantee broken (bfloat16 match scores; a probe walk cut after
its first level). The faults are planted under a whole run of the
harness, past its look for a chip: an answer altered where the program
produces it, half of the batch left out, and an answer that never comes.
"""
import os

import numpy as np
import pytest

from _benchroot import edit_json, run_cell, tiny_root

from bench import control
from bench.harness import spec


def _cfg(root, cell):
    bench = spec.load(root)
    c = spec.cell(bench, cell)
    return spec.config(root, bench, c["config"]), spec.traffic(root,
                                                               c["traffic"])


@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_batch_control_is_not_correct(tmp_path, seed):
    cfg, _ = _cfg(tiny_root(tmp_path), "registry-batch")
    checks = control.batch_control(cfg, seed)
    assert any(v > lim for v, lim in checks.values()), checks
    assert checks["blocks_diff"][0] == checks["pairs_diff"][0] == 0


@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_registry_control_is_not_correct(tmp_path, seed):
    cfg, traffic = _cfg(tiny_root(tmp_path), "registry-probe")
    checks = control.probe_control(cfg, traffic, seed, seconds=2.0)
    assert checks["probes_diff"][0] > checks["probes_diff"][1], checks


def _wrap_dedup(monkeypatch, fault):
    from repro.data import pipeline

    real = pipeline.dedup_corpus

    def broken(corpus, *args, **kwargs):
        return fault(real, corpus, *args, **kwargs)

    monkeypatch.setattr(pipeline, "dedup_corpus", broken)


def _altered_label(real, corpus, *args, **kwargs):
    rep = real(corpus, *args, **kwargs)
    rep.component_of = rep.component_of.copy()
    rep.component_of[-1] = rep.component_of[-1] + 1
    return rep


def _half_batch(real, corpus, *args, **kwargs):
    """Blocks only the first half of the records (the rest hold no key)."""
    import jax.numpy as jnp
    from repro.core.blocks import TokenColumn

    half = corpus.num_records // 2
    cols = {}
    for name, col in corpus.columns.items():
        mask = np.asarray(col.mask).copy()
        mask[half:] = False
        cols[name] = TokenColumn(col.tokens, jnp.asarray(mask))
    corpus.columns = cols
    return real(corpus, *args, **kwargs)


@pytest.mark.parametrize("fault", [_altered_label, _half_batch])
def test_batch_faults_are_not_correct(tmp_path, monkeypatch, fault):
    root = tiny_root(tmp_path)
    _wrap_dedup(monkeypatch, fault)
    rc, line, err, _ = run_cell(root, "registry-batch", monkeypatch)
    assert rc == 0, err
    assert line["correct"] is False, line["checks"]


def _altered_candidate(real, self, keys, valid, **kw):
    out = real(self, keys, valid, **kw)
    for r in out:
        if len(r.candidates):
            r.candidates = r.candidates[1:]
            break
    return out


def _half_rows(real, self, keys, valid, **kw):
    out = real(self, keys, valid, **kw)
    half = len(out) // 2
    empty = np.zeros(0, np.int64)
    for r in out[half:]:
        r.candidates, r.block_sizes = empty, empty
    return out


@pytest.mark.parametrize("fault", [_altered_candidate, _half_rows])
def test_registry_faults_are_not_correct(tmp_path, monkeypatch, fault):
    from repro.streaming.delta import DeltaBlocker

    root = tiny_root(tmp_path)
    real = DeltaBlocker.query_keys

    def broken(self, keys, valid, **kw):
        return fault(real, self, keys, valid, **kw)

    monkeypatch.setattr(DeltaBlocker, "query_keys", broken)
    rc, line, err, _ = run_cell(root, "registry-probe", monkeypatch,
                                seconds=1.5)
    assert rc == 0, err
    assert line["correct"] is False, line["checks"]


def test_registry_lost_probe_is_not_correct(tmp_path, monkeypatch):
    """A micro-batch's last probe gets no response: it never comes."""
    from repro.serving.service import DedupeService

    root = tiny_root(tmp_path)
    real = DedupeService._step_read

    def lossy(self):
        before = len(self.probe_responses)
        real(self)
        if len(self.probe_responses) - before > 1:
            self.probe_responses.pop()

    monkeypatch.setattr(DedupeService, "_step_read", lossy)
    rc, line, err, _ = run_cell(root, "registry-probe", monkeypatch,
                                seconds=1.5)
    assert rc == 0, err
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["probes_lost"]["value"] > 0
    assert line["checks"]["probes_diff"]["value"] == 0


def test_sound_runs_are_correct_on_several_seeds(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    for seed in (11, 2**31 + 1):
        for cell in ("registry-batch", "registry-probe"):
            rc, line, err, _ = run_cell(root, cell, monkeypatch, seed=seed)
            assert rc == 0, err
            assert line["correct"] is True, (cell, seed, line["checks"])




@pytest.mark.parametrize("content_seed", [4, 2**32 + 17, 90001])
def test_sound_runs_are_correct_on_other_corpora(tmp_path, monkeypatch,
                                                 content_seed):
    """The cells fix one corpus; the comparison holds on others too."""
    root = tiny_root(tmp_path)
    edit_json(os.path.join(root, "bench", "configs", "registry.json"),
              content_seed=content_seed)
    for cell in ("registry-batch", "registry-probe"):
        rc, line, err, _ = run_cell(root, cell, monkeypatch, seed=content_seed)
        assert rc == 0, err
        assert line["correct"] is True, (cell, content_seed, line["checks"])
