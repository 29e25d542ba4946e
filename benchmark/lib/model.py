"""Build the system under test for a configuration file: the program's
model config from the source's keys, random weights made on the device
in one jitted call, and a `JaxEngine` with the file's engine sizes.
Every other engine option stays at the program's default: the cells
measure what users get."""

from __future__ import annotations

from typing import Any, Dict, Tuple

from .spec import model_class


def source_keys(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The file's top level holds the source's keys as run.  `--rehearse`
    overlays the file's `rehearse` group (tiny widths for the CPU)."""
    hf = {k: v for k, v in config.items()
          if k not in ("engine", "rehearse")}
    if rehearse:
        hf.update(config["rehearse"].get("model", {}))
    return hf


def build_engine(config: Dict[str, Any], name: str, rehearse: bool
                 ) -> Tuple[Any, Any]:
    """-> (engine, program model config).  Takes the chip."""
    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import JaxEngine
    from dynamo_tpu.models import get_family

    cfg = model_class(config).program_config(
        source_keys(config, rehearse), name)
    sizes = dict(config["engine"])
    if rehearse:
        sizes.update(config["rehearse"].get("engine", {}))
    weights_seed = int(sizes.pop("weights_seed"))
    family = get_family(cfg)
    # one program makes every leaf, in the dtype it is served in
    params = jax.jit(lambda key: family.init_params(cfg, key))(
        jax.random.PRNGKey(weights_seed))
    jax.block_until_ready(params)
    # the file's `engine` group (with the cell's overlay) names the only
    # EngineConfig fields that leave the program's defaults
    eng = JaxEngine(EngineConfig(model_config=cfg, seed=weights_seed,
                                 **sizes), params=params)
    return eng, cfg
