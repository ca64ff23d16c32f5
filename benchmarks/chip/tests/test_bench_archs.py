"""The architecture files under archs/: each config's architectures load
by name, an unknown name fails at load, and the two architectures that
the first cells ran keep their seeded weights and their counts bitwise
(digests and numbers taken from `data.make_weights` and the counts
before they moved into archs/)."""
import copy
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import cells, data

CONFIGS = sorted(p.stem for p in (cells.HERE / "configs").glob("*.json"))
CLIENT = ("init", "runner_fields", "param_count", "fwd_flops", "train_flops",
          "train_bytes", "local_sgd", "accuracy")
GENERATOR = ("init", "program", "program_layout", "serve",
             "param_count", "step_flops", "step_bytes", "sample")

# sha256 over each leaf's path and float32 bytes, in leaf order
WEIGHTS = {
    ("resnet18-cifar10", 2147483711): (
        "b0b8b93bf28b83b55745a196c9de662358b29d2b09202a8befe08e8a7d8bc67b",
        "8eca5b253b53c49c05556c1a7308b8529088d22155a474fc8c64905e282773a1"),
    ("resnet18-gtsrb", 7): (
        "c20ee60f0f0a97b2a5667f51a9e5add2555e48465958b77aeea951599b9c55e8",
        "23d106745c30c07c9d2a1641b3c9359c757473646b145908438906b082cb34f9"),
}
# client: params, train FLOPs, forward FLOPs, bytes of 4 steps of batch 64
CLIENT_COUNTS = {
    10: [11173962, 2887762944.0, 963718656.0, 2248180032.0],
    43: [11190891, 2887864320.0, 963752448.0, 2248823136.0],
}
# generator at a base width: params, step FLOPs, bytes of a step of 900
GENERATOR_COUNTS = {
    (16, 10): [452928, 81798912.0, 1435590912.0],
    (32, 10): [1291904, 320689664.0, 2797154816.0],
    (16, 43): [461376, 81798912.0, 1435624704.0],
    (32, 43): [1300352, 320689664.0, 2797188608.0],
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_config_archs_expose_the_interface(name):
    c = cells.config(name)
    client = cells.arch(c["model"]["arch"])
    gen = cells.arch(c["generator"]["arch"])
    assert all(callable(getattr(client, f)) for f in CLIENT)
    assert all(callable(getattr(gen, f)) for f in GENERATOR)
    assert cells.arch(c["model"]["arch"]) is client     # loaded once


def test_unknown_arch_fails_at_load_naming_the_file(tmp_path, monkeypatch):
    with pytest.raises(FileNotFoundError, match=r"archs/no-such-arch\.py"):
        cells.arch("no-such-arch")
    cfg = json.loads((cells.HERE / "configs" /
                      "resnet18-cifar10.json").read_text())
    cfg["model"]["arch"] = "no-such-arch"
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "x.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(cells, "HERE", tmp_path)
    with pytest.raises(FileNotFoundError, match=r"no-such-arch\.py"):
        cells.config("x")


def test_arch_refuses_a_topology_it_does_not_build():
    m = copy.deepcopy(cells.config("resnet18-cifar10")["model"])
    m["stage_blocks"] = [3, 4, 6, 3]
    with pytest.raises(ValueError, match="stage blocks"):
        cells.arch(m["arch"]).runner_fields(m)


@pytest.mark.parametrize("name,seed", sorted(WEIGHTS))
def test_seeded_weights_are_bitwise_the_parents(name, seed):
    c = cells.config(name)
    client, gen = data.make_weights(seed, c["model"]["num_classes"],
                                    c["model"], c["generator"])
    assert (_digest(client), _digest(gen)) == WEIGHTS[(name, seed)]


@pytest.mark.parametrize("classes", sorted(CLIENT_COUNTS))
def test_client_counts_are_the_parents(classes):
    m = cells.config("resnet18-cifar10")["model"]
    a = cells.arch(m["arch"])
    assert [a.param_count(classes, m), a.train_flops(classes, m),
            a.fwd_flops(classes, m), a.train_bytes(classes, m, 64, 4)] == \
        CLIENT_COUNTS[classes]


@pytest.mark.parametrize("base,classes", sorted(GENERATOR_COUNTS))
def test_generator_counts_are_the_parents(base, classes):
    g = dict(cells.config("resnet18-cifar10")["generator"], base_width=base)
    a = cells.arch(g["arch"])
    assert [a.param_count(classes, g), a.step_flops(classes, g),
            a.step_bytes(classes, g, 900)] == GENERATOR_COUNTS[(base, classes)]


@pytest.mark.parametrize("base", [16, 32])
def test_program_layout_is_the_seeded_weights(base):
    """`build.py` serves seeded weights only where the program's own
    generator for the spec has their layout; another base width has
    another layout."""
    from chipbench.build import _layout
    g = dict(cells.config("resnet18-cifar10")["generator"], base_width=base)
    a = cells.arch(g["arch"])
    seeded = _layout(a.init(jax.random.PRNGKey(0), 10, g))
    assert _layout(a.program_layout(10, g)) == seeded
    other = dict(g, base_width=48 - base)
    assert _layout(a.program_layout(10, other)) != seeded
