"""The plain float32 references against the engine at smoke size on the
CPU: the weights follow the seed's recipe exactly, sound runs agree with the
reference, the float8 control does not, and a run whose served tokens are
altered where they are produced comes out not correct."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct, harness, model, spec
from chipbench.reference import common as ref
from chipbench.reference import family_module

import cb_fixtures

LIMIT = 0.05           # the fixtures' logit-gap limit; sound runs read 0.0-0.02


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cb_fixtures.write_root(tmp_path_factory.mktemp("cb"),
                                  gap_limit=LIMIT)


@pytest.mark.parametrize("conf", [cb_fixtures.TINY_DENSE,
                                  cb_fixtures.TINY_MOE],
                         ids=["dense", "moe"])
def test_weights_follow_the_seeds_recipe(conf):
    from repro.models import api
    seed = 2 ** 31 + 5
    cfg = model.model_config(conf)
    params = api.init_params(cfg, model.param_key(seed))
    key = model.param_key(seed)
    emb = ref.embed_weights(conf, key)
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32))
    assert np.array_equal(f32(params["embed"]["table"]), emb["table"])
    if not conf["tie_word_embeddings"]:
        assert np.array_equal(f32(params["embed"]["head"]), emb["head"])
    family = family_module(conf["family"])
    _, layer_keys = ref.key_tree(key, conf["num_hidden_layers"])
    unit = params["unit"][0]
    names = {"dense": {"gate": "w_gate", "up": "w_up", "down": "w_out"},
             "moe": {"router": "router", "gate": "w_gate", "up": "w_up",
                     "down": "w_out"}}[conf["family"]]
    for layer in range(conf["num_hidden_layers"]):
        w = ref.layer_weights(conf, family, layer_keys[layer])
        for k in ("wq", "wk", "wv", "wo"):
            assert np.array_equal(f32(unit["attn"][k][layer]),
                                  np.asarray(w["attn"][k]))
        for r, p in names.items():
            assert np.array_equal(f32(unit["ffn"][p][layer]),
                                  np.asarray(w["ffn"][r]))


@pytest.mark.parametrize("cell_name", ["tiny-dense.closed", "tiny-moe.open"])
def test_sound_run_agrees_with_the_reference(root, cell_name):
    cell = spec.load_cell(root, cell_name)
    result, run, _ = harness.run_cell(cell, 3, 2.0, False,
                                      time.perf_counter(),
                                      require_tpu=False)
    checks = result["checks"]
    assert result["correct"], checks
    assert run.checked[1] >= 2
    if cell.conf["family"] == "moe":
        assert checks["tokens_dropped"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("conf", [cb_fixtures.TINY_DENSE,
                                  cb_fixtures.TINY_MOE],
                         ids=["dense", "moe"])
def test_float8_control_fails_where_the_program_passes(conf):
    """The same served streams, read by the reference (the program's
    tokens) and by the control (the tokens float8 would serve): the
    program's widest gap is within the limit, the control's is not."""
    from repro.serving import Request, ServingEngine
    seed = 3
    cfg = model.model_config(conf)
    from repro.models import api
    eng = ServingEngine(cfg, api.init_params(cfg, model.param_key(seed)),
                        max_slots=2, t_max=128)
    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(0, conf["vocab_size"], 24 + 16 * i)
                    .astype(np.int32), max_new_tokens=8) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion(200)
    seqs, rows, served = correct.inputs(
        [harness.Track(None, r) for r in reqs])
    program, control = correct.gaps(conf, seed, seqs, rows, served,
                                    control=True)
    assert program <= LIMIT < control


def test_control_in_the_programs_place_is_not_correct(root):
    """A whole run with the control in the program's place: the tokens
    float8 puts first go through the harness's own comparison and come out
    not correct, while the program's reading of the same sample passes."""
    cell = spec.load_cell(root, "tiny-dense.closed")
    result, run, _ = harness.run_cell(cell, 3, 2.0, False,
                                      time.perf_counter(),
                                      require_tpu=False, control=True)
    program, control = run.readings
    assert program <= LIMIT < control
    assert result["checks"]["logit_gap"]["value"] == control
    assert not result["correct"]


def test_a_token_altered_where_produced_is_not_correct(root, monkeypatch):
    from repro.serving.engine import ServingEngine
    inner = ServingEngine._step_inner

    def altered(self, step_no):
        n = inner(self, step_no)
        for req in self.active:
            if req is not None and req.rid >= 0 and len(req.generated) > 2:
                req.generated[-1] = (req.generated[-1] + 1) % 512
        return n

    monkeypatch.setattr(ServingEngine, "_step_inner", altered)
    cell = spec.load_cell(root, "tiny-dense.closed")
    result, _, _ = harness.run_cell(cell, 3, 2.0, False, time.perf_counter(),
                                    require_tpu=False)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > LIMIT


def test_a_step_that_leaves_the_cache_unchanged_is_not_correct(
        root, monkeypatch):
    """The decode step's new KV state dropped: every step decodes against
    the cache as admission left it."""
    from repro.fabric.paged_kv import PagedKVCache
    monkeypatch.setattr(PagedKVCache, "update", lambda self, caches: None)
    cell = spec.load_cell(root, "tiny-dense.closed")
    result, _, _ = harness.run_cell(cell, 5, 3.0, False, time.perf_counter(),
                                    require_tpu=False)
    assert not result["correct"]
    assert result["checks"]["logit_gap"]["value"] > LIMIT


def test_routing_drops_fail_the_moe_check(root):
    cell = spec.load_cell(root, "tiny-moe.open")
    run = harness.Run(cell=cell, seed=0, seconds=1.0, setup_s=0.0, open=0.0,
                      close=1.0, tracks={}, steps=[],
                      deploy={}, device={}, peaks={})
    checks = correct.check(run, cell, 0, dropped=3)
    assert checks["tokens_dropped"] == {"value": 3, "limit": 0}
    assert not correct.passed(checks)
