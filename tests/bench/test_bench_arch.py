"""Architecture modules (``bench/arch/``): the dense GQA module gives
the weights, reference logits and work that the benchmark gave before
the modules existed; an architecture is added as files alone; and the
shared library names none."""
import ast
import hashlib
import json
import pathlib
import types

import jax
import numpy as np
import pytest

import benchkit
from benchkit import REPO
from bench.lib import harness, spec
from bench.lib.weights import make_weights
from bench.lib.work import Work

HERE = pathlib.Path(__file__).resolve().parent
DENSE = spec.load_arch(REPO / "bench/arch/dense_gqa.py")
SEED = 2**40 + 1

#: sha256 (first 16 hex digits) of each leaf's bytes from the seed
#: ``SEED``, recorded with the weights code before it was split into
#: architecture modules (CPU)
QWEN3_SMOKE = {
    "embed/tok": "35c854b964cdaabb",
    "final_norm/scale": "9a1c76ed199fe4fa",
    "layers/ffn/wd": "ae4ecc5a27285f9f",
    "layers/ffn/wg": "34851573b28a4599",
    "layers/ffn/wu": "4b07d5474e24d8f7",
    "layers/ln1/scale": "f0af560c0d776e0f",
    "layers/ln2/scale": "944b8407555bf4ba",
    "layers/mix/k_norm": "14ce842554397840",
    "layers/mix/q_norm": "00ad4e4d0499f24a",
    "layers/mix/wk": "ab324a63487cbf94",
    "layers/mix/wo": "88806d5961f1bbcf",
    "layers/mix/wq": "b4f9ca7acb7874c5",
    "layers/mix/wv": "90178dc3495e1de1",
}
QWEN2_SMOKE = {
    "embed/tok": "35c854b964cdaabb",
    "final_norm/scale": "9a1c76ed199fe4fa",
    "layers/ffn/wd": "ae4ecc5a27285f9f",
    "layers/ffn/wg": "34851573b28a4599",
    "layers/ffn/wu": "4b07d5474e24d8f7",
    "layers/ln1/scale": "f0af560c0d776e0f",
    "layers/ln2/scale": "944b8407555bf4ba",
    "layers/mix/bk": "b33680261e7b67c0",
    "layers/mix/bq": "69253a8db26607f1",
    "layers/mix/bv": "107d52c1987671bb",
    "layers/mix/wk": "f6b5ceb356c37502",
    "layers/mix/wo": "b4f9ca7acb7874c5",
    "layers/mix/wq": "8ae026c5128ed941",
    "layers/mix/wv": "c0824929a80053de",
}
#: the smoke config with qwen2's qkv bias in place of qwen3's qk-norm
BIAS_CONFIG = dict(benchkit.SMOKE_CONFIG,
                   arch={"qk_norm": False, "attention_bias": True})
#: reference logits of TOKENS at ROWS from those weights, recorded with
#: the reference before it moved: (sum, sum of magnitudes, [3, 17])
TOKENS = (np.arange(40) * 37 % 256).astype(np.int32)
ROWS = np.arange(20, 40)
LOGITS = {
    ("qk_norm", None): (19.402023315429688, 652.579833984375,
                        -0.09579778462648392),
    ("qk_norm", "int8"): (18.104524612426758, 652.659423828125,
                          -0.09841272234916687),
    ("qk_norm", "fp8"): (23.163516998291016, 649.3869018554688,
                         -0.13273651897907257),
    ("bias", None): (45.862632751464844, 638.0947265625,
                     -0.1983662098646164),
    ("bias", "int8"): (45.82830047607422, 638.292236328125,
                       -0.19584296643733978),
}
CONFIGS = {"qk_norm": (benchkit.SMOKE_CONFIG, QWEN3_SMOKE),
           "bias": (BIAS_CONFIG, QWEN2_SMOKE)}
UNTIED = dict(benchkit.SMOKE_CONFIG, reference="bench/arch/untied_head.py",
              model=dict(benchkit.SMOKE_CONFIG["model"],
                         tie_word_embeddings=False))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")
    benchkit.smoke_sizes(monkeypatch)


def _weights(arch, config, seed=SEED):
    return make_weights(arch.shapes(arch.dims(config)), arch.std, seed,
                        "bfloat16")


@pytest.mark.parametrize("variant", sorted(CONFIGS))
def test_dense_weights_are_bit_for_bit_as_before(variant):
    config, want = CONFIGS[variant]
    flat = jax.tree_util.tree_flatten_with_path(_weights(DENSE, config))[0]
    got = {"/".join(p.key for p in path):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for path, x in flat}
    assert got == want


@pytest.mark.parametrize("variant,quant", sorted(LOGITS, key=str))
def test_dense_reference_logits_are_as_before(variant, quant):
    config = CONFIGS[variant][0]
    lg = np.asarray(DENSE.logits(_weights(DENSE, config),
                                 DENSE.dims(config), TOKENS, ROWS,
                                 quant=quant))[:len(ROWS)]
    total, mag, one = LOGITS[variant, quant]
    # the same computation on the same CPU; the tolerance only allows
    # for a reduction split another way on another core count
    assert float(lg.sum()) == pytest.approx(total, rel=1e-5)
    assert float(np.abs(lg).sum()) == pytest.approx(mag, rel=1e-6)
    assert float(lg[3, 17]) == pytest.approx(one, rel=1e-5)


def test_each_traced_dispatch_is_charged_its_rows_and_the_weights_once(
        tmp_path):
    """Over a traced smoke window, every dispatch's work is its decode
    rows plus its prefill rows, with every weight read once, a fused
    dispatch included: the sum the record made before the work moved
    into the architecture module."""
    calls = []

    def spy(kind, fn):
        def call(*a):
            if kind == "chunk":
                keep = (None, None, a[4], a[5])
            else:
                keep = (a[1]["lengths"], a[2].shape[1]) + (
                    (a[5], a[6]) if kind == "fused" else (None, None))
            calls.append((kind, tuple(
                np.array(x) if isinstance(x, np.ndarray) else x
                for x in keep)))
            return fn(*a)
        return call

    root = benchkit.make_root(tmp_path)
    runner = harness.Runner(spec.load_cell("smoke.chat", root=root),
                            traced=True, fault=spy)
    runner.build(11)
    runner.measure(11, 3.0)
    runner.free()
    ds = runner.recorder.dispatches()
    k = runner.k
    assert [d.kind for d in ds] == [c[0] for c in calls[-len(ds):]]
    assert {"decode", "chunk", "fused"} <= {d.kind for d in ds}
    for d, (kind, (lengths, T, starts, valid)) in zip(ds, calls[-len(ds):]):
        want = Work(weight_bytes=DENSE.weight_bytes(k))
        if kind != "chunk":
            lengths = np.asarray(lengths)
            want = want + DENSE.decode_rows(k, lengths[lengths > 0], T=T)
        if kind != "decode":
            want = want + DENSE.prefill_rows(k, np.asarray(starts),
                                             np.asarray(valid))
        assert d.work == want, (kind, d.work, want)


def _changed(root: pathlib.Path):
    """Files of ``root``'s ``bench/`` that differ from the repo's, and
    files it has that the repo's lacks."""
    edited, added = [], []
    for p in sorted((root / "bench").rglob("*")):
        if not p.is_file() or "__pycache__" in p.parts:
            continue
        rel = p.relative_to(root)
        mine = REPO / rel
        if not mine.is_file():
            added.append(str(rel))
        elif mine.read_bytes() != p.read_bytes():
            edited.append(str(rel))
    return edited, added


@pytest.fixture(scope="module")
def untied_root(tmp_path_factory):
    return benchkit.make_root(tmp_path_factory.mktemp("untied"),
                              config=UNTIED, arch=HERE / "untied_head.py")


def test_the_dense_module_refuses_an_untied_head():
    with pytest.raises(ValueError, match="only tied embeddings"):
        DENSE.program_config(UNTIED)


@pytest.mark.parametrize("trace", [0, 1])
def test_an_architecture_added_as_files_runs_end_to_end(untied_root, capsys,
                                                        trace):
    edited, added = _changed(untied_root)
    assert edited == []                  # bench/lib/ and the rest as is
    assert "bench/arch/untied_head.py" in added
    cell = spec.load_cell("smoke.chat", root=untied_root)
    k = cell.arch.dims(cell.config)
    assert cell.arch.shapes(k)["embed"]["head"] == (64, 256)
    # a dispatch reads the head too: 64 x 256 bf16
    assert cell.arch.dispatch_work(k, decode=([3], 1)).weight_bytes == (
        DENSE.weight_bytes(k) + 64 * 256 * 2)
    rc = harness.main(["--workload", "smoke.chat", "--seed", str(2**40 + 9),
                       "--seconds", "3", "--trace", str(trace)],
                      require_accelerator=False, root=untied_root,
                      peaks=benchkit.CPU_PEAKS)
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    if trace:
        assert res["metrics"]["step.mfu"]["value"] > 0
    else:
        assert res["metrics"]["output_tok_s"]["value"] > 0


def test_a_layout_that_is_not_the_programs_is_refused(untied_root):
    """A module whose shapes leave out a leaf of the program's tree
    fails the layout check, with its message."""
    cell = spec.load_cell("smoke.chat", root=untied_root)
    cell.arch = types.SimpleNamespace(
        **{n: getattr(cell.arch, n) for n in spec.ARCH_NAMES})
    cell.arch.shapes = DENSE.shapes                # no output head
    runner = harness.Runner(cell)
    with pytest.raises(ValueError, match="weight layout differs from the "
                                         "program's"):
        runner.build(1)


def _lib_modules():
    return sorted((REPO / "bench/lib").glob("*.py"))


def _imports(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield "." * node.level + (node.module or "")


def _leaf_names():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = set()
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        arch = spec.load_arch(REPO / cfg["reference"])
        flat = jax.tree_util.tree_flatten_with_path(
            arch.shapes(arch.dims(cfg)),
            is_leaf=lambda x: isinstance(x, tuple))[0]
        names |= {path[-1].key for path, _ in flat}
    return names


@pytest.mark.parametrize("path", _lib_modules(), ids=lambda p: p.name)
def test_the_library_names_no_architecture(path):
    tree = ast.parse(path.read_text())
    for name in _imports(tree):
        assert "arch" not in name.split("."), (path.name, name)
    strings = {n.value for n in ast.walk(tree)
               if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    assert not strings & _leaf_names(), path.name


@pytest.mark.parametrize("config", [
    c["name"] for c in json.loads((REPO / "BENCHMARK.json").read_text())[
        "configs"]])
def test_each_configuration_names_a_whole_architecture_module(config):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[config]
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert cfg["reference"].startswith("bench/arch/")
    arch = spec.load_arch(REPO / cfg["reference"])
    for name in spec.ARCH_NAMES:
        assert hasattr(arch, name), name
    k = arch.dims(cfg)
    assert isinstance(k, arch.Dims) and k.V == cfg["model"]["vocab_size"]
    hash(k)
