"""Where what the families share lives: the expert layer in
models/moe.py, the burst scan, the one-row prefill and the pool index
in models/common.py, and nowhere else; and no config picks a dispatch."""

import ast
import dataclasses
import fnmatch
import os

import pytest

from dynamo_tpu.models import (
    Cohere2Config,
    DeepseekConfig,
    KeyeConfig,
    LingConfig,
    LlamaConfig,
    MimoConfig,
    NemotronHConfig,
)

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "dynamo_tpu")
SHARED = ("moe", "common")          # modules of models/ that own the names
FAMILIES = ("llama", "deepseek", "mimo", "keye", "ling", "nemotron_h",
            "cohere2")
PATTERNS = ("moe_*", "*_router", "pool_index", "burst_scan")
# (module, name): why the module binds a shared name it does not use
REEXPORTS = {("llama", "experts_held"):
             "benchmark/reference/keye.py reads it from models.llama, and "
             "a simplicity PR may not edit benchmark/"}


def _shared(name: str) -> bool:
    return any(fnmatch.fnmatch(name.lstrip("_"), p) for p in PATTERNS)


def _modules(sub):
    d = os.path.join(PKG, sub)
    for f in sorted(os.listdir(d)):
        if f.endswith(".py"):
            with open(os.path.join(d, f)) as fh:
                yield f[:-3], ast.parse(fh.read())


def test_the_expert_layer_and_the_burst_scan_have_one_home():
    owned = set()
    for sub in ("models", "engine"):
        for mod, tree in _modules(sub):
            own = sub == "models" and mod in SHARED
            used = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
            for node in tree.body:          # a module's own functions
                if isinstance(node, ast.FunctionDef) and _shared(node.name):
                    assert own, f"{sub}/{mod}.py defines {node.name}"
                    owned.add(node.name)
            for node in ast.walk(tree):
                if not isinstance(node, ast.ImportFrom):
                    continue
                source = (node.module or "").split(".")[-1]
                if own:
                    assert source not in FAMILIES, \
                        f"models/{mod}.py imports from family {source}"
                for name in (a.name for a in node.names):
                    if source in FAMILIES:
                        assert not _shared(name), \
                            f"{sub}/{mod}.py imports {name} from {source}"
                    elif (sub == "models" and source in SHARED
                            and name not in used):
                        assert (mod, name) in REEXPORTS, \
                            f"models/{mod}.py re-exports {name}"
    assert {"moe_dispatch", "moe_form", "moe_rows", "softmax_router",
            "ds_router", "pool_index", "burst_scan"} <= owned


CONFIGS = [LlamaConfig, DeepseekConfig, MimoConfig, KeyeConfig, LingConfig,
           NemotronHConfig, Cohere2Config]


@pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c.__name__)
def test_no_config_picks_a_dispatch(config):
    """The dropless dispatch is the one mathematics; its form follows
    the program's shape (moe.moe_dispatch_form).  A config that names
    one is refused as any unknown field is."""
    for field in dataclasses.fields(config):
        assert "dispatch" not in field.name and "capacity" not in field.name
    with pytest.raises(TypeError):
        config(moe_dispatch="dense")
    with pytest.raises(TypeError):
        config(moe_capacity_factor=1.25)
