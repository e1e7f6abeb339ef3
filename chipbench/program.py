"""The system under test as the benchmark reaches it: the repository's
model config with the sizes of the configuration file, its compile cache,
and ``ServeLoop``. Every import of the program is here."""

from __future__ import annotations

import dataclasses

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache  # noqa: F401
from repro.models import lm
from repro.serve import ServeLoop

# keys of a configuration file that are fields of the program's config
FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
          "d_ff", "vocab_size", "rope_theta", "norm_eps", "tie_embeddings",
          "mlp_kind", "param_dtype", "compute_dtype", "use_pallas")


def model_config(conf: dict):
    """The repository's config for ``arch_id`` with every size the file
    states, so that the file is what runs."""
    cfg = get_config(conf["arch_id"])
    kw = {k: conf[k] for k in FIELDS if k in conf}
    if "ssm" in conf:
        kw["ssm"] = dataclasses.replace(cfg.ssm, **conf["ssm"])
    return cfg.replace(**kw)


def check_layout(cfg, params) -> None:
    """The benchmark's weights must have the program's tree, shapes and
    dtypes."""
    want = lm.abstract_params(cfg)
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if want != got:
        raise SystemExit("chipbench: the seeded weights do not have the "
                         f"program's layout:\n want {want}\n got  {got}")


def serve_loop(cfg, params, max_len: int) -> ServeLoop:
    return ServeLoop(cfg, params, max_len=max_len)
