"""`ray_tpu/models/` has a bottom layer and two ways out: the families
stand on `models/blocks.py`, `models/family.py` and `ops/`, none reads
another module's private name, and a trainer is found from the
configuration (`cfg.training`) as an engine's family is (`cfg.family`).
Held by `ast`, as `tests/test_one_layer.py` holds the one layer."""

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import family, latent_sparse_moe as lsm, \
    window_moe_train as wmt
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import spmd

PACKAGE = pathlib.Path(family.__file__).parent.parent        # ray_tpu/
MODELS = PACKAGE / "models"
HELD = sorted(MODELS.glob("*.py")) + [PACKAGE / "train" / "spmd.py"]
# what two or more families compute the same way: each has one definition
ONCE = ("mm", "rms_norm", "layer_norm", "rope_halves", "rope_pairs",
        "unembed", "gated_mlp", "write_rows", "write_chunk", "row_index",
        "copy_block", "gather_block", "scatter_block", "routing",
        "expert_layer", "rounded", "summarize")
ALIASES = {"latent_moe_loss_fn": "features_loss_fn",
           "window_moe_loss_fn": "features_loss_fn",
           "make_latent_moe_trainer": "make_features_trainer",
           "make_window_moe_trainer": "make_features_trainer"}


def private(name: str) -> bool:
    return name.startswith("_") and not name.startswith("__")


def reads_of_private_names(path):
    """[(line, what)]: every `from ray_tpu... import _name`, and every
    `_name` read off a name that an import from `ray_tpu` bound, anywhere
    in the file (function-level imports too)."""
    tree = ast.parse(path.read_text())
    found, bound = [], set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and (node.module or "").startswith(
                "ray_tpu"):
            for a in node.names:
                bound.add(a.asname or a.name)
                if private(a.name):
                    found.append((node.lineno, f"{node.module}.{a.name}"))
        elif isinstance(node, ast.Import):
            bound.update(a.asname or a.name.split(".")[0] for a in node.names
                         if a.name.startswith("ray_tpu"))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and private(node.attr):
            base = node.value
            while isinstance(base, ast.Attribute):
                base = base.value
            if isinstance(base, ast.Name) and base.id in bound:
                found.append((node.lineno, f"{base.id}.{node.attr}"))
    return found


@pytest.mark.parametrize("path", HELD, ids=lambda p: p.name)
def test_no_module_reads_another_s_private_name(path):
    assert reads_of_private_names(path) == []


def test_nothing_in_the_package_imports_a_private_name_of_a_family():
    found = []
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and (
                    node.module or "").startswith("ray_tpu.models"):
                found += [(path.name, node.module, a.name)
                          for a in node.names if private(a.name)]
    assert found == []


def model_imports():
    """{module: the modules of `ray_tpu/models/` it imports}."""
    names = {p.stem for p in MODELS.glob("*.py")}
    graph = {}
    for path in MODELS.glob("*.py"):
        deps = set()
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and node.module:
                if node.module == "ray_tpu.models":
                    deps.update(a.name for a in node.names)
                elif node.module.startswith("ray_tpu.models."):
                    deps.add(node.module.split(".")[2])
            elif isinstance(node, ast.Import):
                deps.update(a.name.split(".")[2] for a in node.names
                            if a.name.startswith("ray_tpu.models."))
        graph[path.stem] = deps & names
    return graph


def test_blocks_is_the_bottom_and_the_families_form_no_cycle():
    graph = model_imports()
    assert graph["family"] == set()
    assert graph["blocks"] <= {"family"}
    for name in ("gpt", "latent_sparse_moe", "retention", "window_moe",
                 "mamba_moe", "window_moe_train"):
        assert graph[name] == {"blocks", "family"}, name
    done, path = set(), []

    def visit(name):
        assert name not in path, path + [name]
        if name not in done:
            path.append(name)
            for dep in sorted(graph[name]):
                visit(dep)
            path.pop()
            done.add(name)

    for name in sorted(graph):
        visit(name)


@pytest.mark.parametrize("name", ONCE)
def test_a_shared_piece_is_defined_once_and_in_blocks(name):
    where = [path.name for path in sorted(MODELS.glob("*.py"))
             for node in ast.walk(ast.parse(path.read_text()))
             if isinstance(node, ast.FunctionDef) and node.name == name]
    assert where == ["blocks.py"]


def test_the_four_pinned_trainer_names_are_bindings_without_a_body():
    tree = ast.parse((PACKAGE / "train" / "spmd.py").read_text())
    defined = {n.name for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef)}
    assert not defined & set(ALIASES)
    assert {"features_loss_fn", "make_features_trainer"} <= defined
    for alias, target in ALIASES.items():
        assert getattr(spmd, alias) is getattr(spmd, target)


def latent_cfg():
    return lsm.LatentSparseMoEConfig(
        q_rank=None, index_topk=None, indexer_types=("none",) * 3,
        dtype="float32")


def window_cfg():
    return wmt.WindowMoETrainConfig(dtype="float32")


def text(fn, *args):
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


@pytest.mark.parametrize("make_cfg,module,trainer,loss", [
    (latent_cfg, lsm, "make_latent_moe_trainer", "latent_moe_loss_fn"),
    (window_cfg, wmt, "make_window_moe_trainer", "window_moe_loss_fn"),
], ids=["latent_moe", "window_moe"])
def test_a_pinned_trainer_is_the_features_trainer_of_its_family(
        make_cfg, module, trainer, loss):
    """`cfg.training` names the module's own functions, and the trainer
    the configuration file calls by name makes the step
    `make_features_trainer` makes: the same program, loss and gradient
    and the whole step."""
    cfg = make_cfg()
    fam = cfg.training
    assert isinstance(fam, family.TrainingFamily)
    assert (fam.init_params, fam.param_logical_axes,
            fam.forward_features) == (module.init_params,
                                      module.param_logical_axes,
                                      module.forward_features)
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    batch = {"inputs": tokens, "targets": tokens}
    state, named, _ = getattr(spmd, trainer)(cfg, mesh)
    _, shared, _ = spmd.make_features_trainer(cfg, mesh, init_state=False)
    assert text(named, state, batch) == text(shared, state, batch)

    def by(fn):
        return text(lambda p, b: jax.value_and_grad(
            lambda q: fn(q, b, cfg, mesh))(p), state.params, batch)

    assert by(getattr(spmd, loss)) == by(spmd.features_loss_fn)
    if fam.frozen is not None:      # what the optimizer never sees
        assert any(jax.tree.leaves(fam.frozen(state.params)))
