"""The architecture seam: what each configuration's module gives is pinned
to the values the dense transformer gave before it moved behind the seam,
and the harness takes the architecture from the module alone."""
import jax
import numpy as np
import pytest

import flops
import harness
import reference as R
import traffic
from weights import make_batch

# Computed before the dense transformer moved into arch/dense.py: the
# weights' checksum (seed 7), model FLOPs per token at seq 1024, the stats
# bytes of a `health` step, and, at smoke width, the reference's losses
# over the first three batches of seed 7 (batch 2 x seq 16). The full
# configurations' trees are 2 GB each; their checksum is taken at their
# own widths with one layer and a vocabulary of 256.
PINS = {
    "qwen2-0.5b": ((154326.52162746835, 3433851.9245882942), 3228008448.0,
                   3203399688, None),
    "phi4-mini-3.8b-stage4": ((258338.4042956846, 9143953.651591057),
                              3027861504.0, 838860808, None),
    "smoke": ((13383.65465267769, 70779.39638037371), 2207232.0, 13107208,
              (6.266119130452474, 6.224617767333984, 6.23465576171875)),
}


def _checksum(params):
    """Sums of squares and of magnitudes, each leaf weighted by its place
    in name order, so a leaf's values under another name also count."""
    leaves = sorted((R.leaf_name(p), np.asarray(v, np.float64))
                    for p, v in jax.tree_util.tree_leaves_with_path(params))
    return (sum((i + 1) * float(np.sum(v * v))
                for i, (_, v) in enumerate(leaves)),
            sum((i + 1) * float(np.sum(np.abs(v)))
                for i, (_, v) in enumerate(leaves)))


@pytest.mark.parametrize("name", list(PINS))
def test_dense_values_are_pinned(name, tiny_model):
    cfg = harness.config("qwen2-0.5b" if name == "smoke" else name)
    model = tiny_model if name == "smoke" else cfg["model"]
    arch = harness.arch_module({**cfg, "model": model})
    checksum, flops_per_token, stats_bytes, losses = PINS[name]
    held = model if name == "smoke" else {**model, "num_layers": 1,
                                          "vocab_size": 256}
    # float64 sums of the float32 leaves: rounding alone moves them far
    # less than a thousandth of a millionth
    assert _checksum(arch.make_params(7, held)) == \
        pytest.approx(checksum, rel=1e-9)
    assert arch.flops_per_token(model, 1024) == flops_per_token
    assert flops.stats_bytes_per_step(arch, model, traffic.load("health")) \
        == stats_bytes
    if losses:
        batches = [make_batch(7, s, 2, 16, model["vocab_size"])
                   for s in range(3)]
        ref = arch.train_steps(7, model, cfg["train"], batches)
        # float32 products at HIGHEST on another CPU may round otherwise
        assert ref["loss"] == pytest.approx(losses, rel=1e-6)


COUNTING = '''
import collections
from arch import dense

CALLS = collections.Counter()


def _counted(name):
    fn = getattr(dense, name)

    def counted(*a, **k):
        CALLS[name] += 1
        return fn(*a, **k)
    return counted


for _name in {functions!r}:
    globals()[_name] = _counted(_name)
'''


def test_the_harness_takes_the_architecture_from_its_module(
        smoke, tiny_model, tmp_path, monkeypatch):
    direct = smoke("health")
    (tmp_path / "counting.py").write_text(
        COUNTING.format(functions=harness.ARCH_FUNCTIONS))
    (tmp_path / "partial.py").write_text(
        "from arch.dense import " + ", ".join(
            f for f in harness.ARCH_FUNCTIONS if f != "site_bytes") + "\n")
    monkeypatch.setattr(harness, "ARCH_DIR", tmp_path)
    config = harness.config
    named = {}
    monkeypatch.setattr(harness, "config",
                        lambda name: {**config(name), **named})

    named["arch"] = "counting"
    seam = smoke("health")
    assert seam["checks"] == direct["checks"]
    calls = harness._load_arch(tmp_path / "counting.py").CALLS
    assert {f: calls[f] > 0 for f in harness.ARCH_FUNCTIONS} == \
        {f: True for f in harness.ARCH_FUNCTIONS}

    # a module without the whole contract is refused by name, before the
    # program runs
    from repro.launch import train
    monkeypatch.setattr(train, "run_training", None)
    named["arch"] = "partial"
    with pytest.raises(harness.HarnessError, match="lacks site_bytes"):
        smoke("health")
    named["arch"] = "absent"
    with pytest.raises(harness.HarnessError, match="'absent'"):
        smoke("health")

    monkeypatch.setattr(harness, "ARCH_DIR", harness.BENCH_DIR / "arch")
    for key, value in (("num_experts", 4), ("tie_embeddings", False),
                       ("family", "moe"), ("rope_kind", "mrope"),
                       ("moe_d_ff", 1408)):
        with pytest.raises(harness.HarnessError, match=key):
            harness.arch_module({"arch": "dense",
                                 "model": {**tiny_model, key: value}})
