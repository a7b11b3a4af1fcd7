"""End-to-end behaviour tests: the full pipeline the framework exists for.

graph generation → random-walk corpus → LM training → checkpoint →
restart → serving, all through the public API.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, applicable_shapes, get_config
from repro.core import (FactionSpec, PBAConfig, PKConfig, degree_counts,
                        fit_power_law, generate_pk_host,
                        make_factions, star_clique_seed)
from repro.models import build_model
from repro.serve.engine import Engine, Request
from repro.train.checkpoint import latest_checkpoint, load_checkpoint, \
    save_checkpoint
from repro.train.data import WalkCorpus, WalkCorpusConfig, batches
from repro.train.optimizer import AdamWConfig, init_opt_state, \
    opt_state_struct
from repro.train.train_step import make_train_step


def test_end_to_end_generate_train_serve(tmp_path):
    """The paper's generator as data infrastructure, end to end."""
    # 1. generate a scale-free graph (PBA, the paper's method)
    corpus = WalkCorpus(WalkCorpusConfig(generator="pba", num_vertices=2048,
                                         vocab_size=512, seed=3))
    deg = corpus.deg
    assert fit_power_law(deg, kmin=4).gamma_mle > 1.5  # scale-free-ish input

    # 2. train a reduced qwen on walk windows
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = build_model(cfg, tp=1, compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=2e-3,
                                                      warmup_steps=5)))
    it = batches(corpus, 8, 64)
    first = last = None
    for i in range(10):
        b = {k: jnp.asarray(v) for k, v in next(it).items()}
        params, opt, m = step(params, opt, b)
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first

    # 3. checkpoint + restart preserves the trajectory
    save_checkpoint(str(tmp_path), 10, params, opt, {"data": corpus.state()})
    p2, o2, man = load_checkpoint(latest_checkpoint(str(tmp_path)),
                                  model.param_struct(),
                                  opt_state_struct(model.param_struct()))
    assert man["step"] == 10

    # 4. serve from the trained weights
    engine = Engine(model, params, batch_size=2, max_len=96)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
                    max_new_tokens=8) for i in range(3)]
    outs = engine.run(reqs)
    assert sorted(c.rid for c in outs) == [0, 1, 2]
    for c in outs:
        assert 1 <= len(c.tokens) <= 8
        assert (c.tokens >= 0).all() and (c.tokens < cfg.vocab_size).all()


def test_engine_eos_stops_early():
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = build_model(cfg, tp=1, compute_dtype=jnp.float32)
    params = model.init(jax.random.key(1))
    # find whichever token the model emits first and treat it as EOS
    engine = Engine(model, params, batch_size=1, max_len=48)
    req = Request(0, np.arange(8, dtype=np.int32), max_new_tokens=6)
    first = engine.run([req])[0].tokens[0]
    engine_eos = Engine(model, params, batch_size=1, max_len=48,
                        eos_id=int(first))
    out = engine_eos.run([req])[0]
    assert len(out.tokens) == 1 and out.tokens[0] == first


def test_pk_graph_feeds_pipeline():
    corpus = WalkCorpus(WalkCorpusConfig(generator="pk", pk_levels=4,
                                         vocab_size=256, seed=1))
    b = corpus.next_batch(4, 32)
    assert b["tokens"].shape == (4, 32)
    assert b["tokens"].max() < 256


def test_shape_cell_accounting():
    """40 assigned cells = 32 runnable + 8 documented long_500k skips."""
    runnable = skipped = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        shapes = applicable_shapes(cfg)
        runnable += len(shapes)
        skipped += 4 - len(shapes)
    assert runnable == 32 and skipped == 8
    # the sub-quadratic families run long_500k
    assert "long_500k" in applicable_shapes(get_config("mamba2-130m"))
    assert "long_500k" in applicable_shapes(get_config("recurrentgemma-2b"))


def test_dryrun_records_complete():
    """All 64 compiled cells exist with the roofline fields (if generated)."""
    import glob
    import json
    import os
    recs = glob.glob("results/dryrun/*.json")
    if not recs:
        pytest.skip("dry-run artifacts not generated in this checkout")
    assert len(recs) == 64
    for path in recs:
        with open(path) as f:
            r = json.load(f)
        pd = r["per_device"]
        assert pd["flops"] > 0
        assert pd["bytes_accessed"] > 0
        assert pd["temp_bytes"] >= 0
