"""Model families (bench/families/): found by a configuration's ``family``
and added by files alone; the dense family reads as the LM yardstick read
before it moved there; the recsys family's traffic and FLOP rule."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, flops, harness, reference
from bench.kinds import train

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _ints(x) -> int:
    x = np.asarray(x, np.int64).ravel()
    return int(np.dot(x, np.arange(x.size) % 997 + 1))


def _floats(x) -> list:
    x = np.asarray(x, np.float64).ravel()
    return [float(np.dot(x, np.sin(np.arange(x.size)))), float(np.dot(x, x))]


# --- found by name, added by files ------------------------------------------


def test_every_configuration_has_its_family():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        cfg = _config(c["name"])
        family = families.load(cfg)
        assert family.__name__ == f"bench.families.{cfg['family']}"
        for name in ("ring", "targets_per_batch", "hidden", "head_table",
                     "matmul_params", "flops_per_target", "GENERATORS"):
            assert hasattr(family, name), (cfg["family"], name)


def test_a_family_is_found_by_its_generator():
    assert families.with_generator("markov_lm").__name__.endswith(".dense")
    assert families.with_generator("zipf_recsys").__name__.endswith(".recsys")
    with pytest.raises(ValueError):
        families.with_generator("no_such_generator")


def test_a_family_is_added_by_files_alone(tmp_path):
    """A copy of the benchmark with one more family file and a
    configuration naming it: the copy's own harness finds the family, its
    ring and its FLOP count, with no other file edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    fam = tmp_path / "bench" / "families"
    src = (fam / "dense.py").read_text()
    (fam / "toy.py").write_text(src.replace('("markov_lm",)', '("toy_lm",)')
                                .replace('== "markov_lm"', '== "toy_lm"'))
    cfg = dict(_config("starcoder2-3b.l7"), name="toy", family="toy")
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    probe = (
        "import json, jax\n"
        "from bench import families, flops, harness\n"
        "cfg = harness.load_json(harness.pathlib.Path('bench/configs/"
        "toy.json'))\n"
        "fam = families.load(cfg)\n"
        "mix = {'generator': 'toy_lm', 'batch': 1, 'seq_len': 8, 'ring': 2,"
        " 'markov_rank': 4, 'temperature': 1.0}\n"
        "ring = jax.eval_shape(lambda k: fam.ring(dict(cfg, vocab_size=64),"
        " mix, k), jax.random.PRNGKey(0))\n"
        "print(json.dumps([fam.__file__, families.with_generator('toy_lm')"
        ".__name__, flops.flops_per_target(cfg, 2048), len(ring),"
        " list(ring[0]['tokens'].shape)]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    path, name, fpt, n, shape = json.loads(p.stdout.strip().splitlines()[-1])
    assert path == str(tmp_path / "bench" / "families" / "toy.py")
    assert name == "bench.families.toy"
    assert fpt == flops.flops_per_target(_config("starcoder2-3b.l7"), 2048)
    assert (n, shape) == (2, [1, 8])


# --- the dense family reads as before the move ------------------------------

#: the seed of the readings below
SEED = 2_147_483_777
#: the LM yardstick's readings at the test size (conftest's ``lm`` cell,
#: seed SEED) before its code moved into bench/families/dense.py: the
#: ring's tokens and labels, the reference's hidden states in each mode,
#: its shared draw, its two steps (losses, then per-leaf gradient and change
#: norms), and the FLOPs per target of starcoder2-3b.l7 at 2048 tokens and
#: of the test size at 64.  Integers and float32 readings folded into
#: float64 sums; a move that changes no arithmetic reads them exactly.
BEFORE = {
    "ring": [8375554, 8436834],
    "hidden_fp32": [10.951586825076673, 4095.9677426443063],
    "hidden_bf16": [10.912570572228688, 4095.9678111322887],
    "hidden_fp8": [8.478170828970939, 4095.9666894110956],
    "draw": [485726, [-3.9791163849689353, 2412.310647350181]],
    "steps": [[6.312894821166992, 6.124890327453613],
              [-0.040571973630229505, 0.9999999749350665],
              [-0.003488440711952383, 0.0016004767346129711]],
    "flops": [4331685888.0, 418176.0],
}


@pytest.fixture(scope="module")
def dense_run(lm):
    inp = train.Inputs(lm, SEED)
    ring = inp.ring()
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), inp.params0())
    return lm, inp, ring, p, families.load(lm.config)


def test_dense_ring_reads_as_before(dense_run):
    _, _, ring, _, _ = dense_run
    assert [_ints([b["tokens"] for b in ring]),
            _ints([b["labels"] for b in ring])] == BEFORE["ring"]


@pytest.mark.parametrize("mode", ["fp32", "bf16", "fp8"])
def test_dense_hidden_reads_as_before(dense_run, mode):
    cell, _, ring, p, fam = dense_run
    h, _ = jax.jit(lambda p, b: fam.hidden(p, b, cell.config, mode))(
        p, ring[0])
    assert _floats(h) == BEFORE[f"hidden_{mode}"]


def test_dense_draw_and_steps_read_as_before(dense_run):
    cell, inp, ring, p, fam = dense_run
    keys = inp.step_keys()
    h, _ = fam.hidden(p, ring[0], cell.config)
    ids, logq = jax.jit(lambda h, w, k: reference.draw(
        h, w, k, cell.config, inp.proj()))(h, fam.head_table(p), keys[0])
    assert [_ints(ids), _floats(logq)] == BEFORE["draw"]
    ref = inp.reference(ring[:2], keys[:2])
    assert [ref["loss"], _floats(ref["grad_norm"]),
            _floats(ref["change_norm"])] == BEFORE["steps"]


def test_dense_flops_read_as_before(lm):
    assert [flops.flops_per_target(_config("starcoder2-3b.l7"), 2048),
            flops.flops_per_target(lm.config, lm.traffic)] == BEFORE["flops"]
    assert flops.flops_per_target(lm.config, 64) == BEFORE["flops"][1]


# --- the recsys family ------------------------------------------------------


def test_youtube_dnn_1m_flops_by_hand():
    cfg = _config("youtube-dnn-1m")
    tower = 320 * 1024 + 1024 * 512 + 512 * 256     # 64 + 256 in
    assert flops.backbone_matmul_params(cfg) == tower == 983_040
    want = 6 * tower + 6 * (1 + 128) * 256          # + positive, 128 negs
    mix = {"batch": 128}
    assert flops.flops_per_target(cfg, mix) == pytest.approx(want)
    assert want == 6_096_384
    assert families.load(cfg).targets_per_batch(cfg, mix) == 128


def test_zipf_ids_are_seeded_in_range_and_zipf():
    from bench.families.recsys import zipf_ids

    n, draws = 1000, 400_000
    f = jax.jit(lambda k: zipf_ids(k, (draws,), n, 1.0))
    a, b = f(jax.random.PRNGKey(3)), f(jax.random.PRNGKey(3))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, f(jax.random.PRNGKey(4)))
    a = np.asarray(a)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < n
    harmonic = np.sum(1.0 / np.arange(1, n + 1))
    counts = np.bincount(a, minlength=n)
    for k in range(1, 11):           # rank k is id k - 1
        p = 1.0 / (k * harmonic)
        sigma = np.sqrt(draws * p * (1 - p))
        assert abs(counts[k - 1] - draws * p) < 5 * sigma, k


def test_recsys_ring_matches_the_programs_batch_layout():
    """The mix's batches at the cell's full size have the shapes and types
    the program's recsys backbone takes (shapes only; nothing is made)."""
    from repro.models import api

    cell = harness.load_cell(ROOT, "train.youtube-dnn-1m")
    ring = jax.eval_shape(lambda k: train.make_ring(cell.config, cell.traffic,
                                                    k), jax.random.PRNGKey(0))
    want = api.train_batch_specs(train.arch_config(cell.config),
                                 cell.traffic["batch"], 0)
    assert len(ring) == cell.traffic["ring"]
    for batch in ring:
        assert {k: (v.shape, v.dtype) for k, v in batch.items()} == {
            k: (v.shape, v.dtype) for k, v in want.items()}
