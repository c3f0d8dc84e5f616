"""From a configuration file to the program's objects, by dotted path.

Runs in the process that holds the chip (replica or train worker), never in
the driver. A family with another config class, initialiser or loss is data
in the configuration file, not a branch here.
"""

from __future__ import annotations

import time

from perfbench.manifest import resolve

#: this process's set-up seconds, read back by the runner
SETUP = {}


def shape_of(config: dict, rehearse: bool) -> dict:
    """The published keys as they are run: the file's, or — rehearsing on
    the CPU — the file's toy widths with the same ratios. Every top-level
    number, bool and null travels, and besides them whatever
    ``program.config_kwargs`` names, of any JSON type (a layer pattern is a
    string, a list of layer kinds a list): a key the config class is built
    from reaches it, and the reference, without an edit here."""
    named = set((config.get("program") or {}).get("config_kwargs", {}).values())
    shape = {k: v for k, v in config.items()
             if k in named or isinstance(v, (int, float, bool)) or v is None}
    if rehearse:
        shape.update(config["rehearsal"]["shape"])
    return shape


def section(config: dict, key: str, rehearse: bool) -> dict:
    out = dict(config.get(key) or {})
    if rehearse:
        out.update(config["rehearsal"].get(key) or {})
    return out


def model_config(config: dict, shape: dict, **overrides):
    import jax.numpy as jnp

    prog = config["program"]
    kwargs = {field: shape[key]
              for field, key in prog["config_kwargs"].items()}
    kwargs["dtype"] = getattr(jnp, prog["dtype"])
    kwargs.update(overrides)
    return resolve(prog["config_class"])(**kwargs)


def init_weights(config: dict, cfg, seed: int, out_shardings=None):
    """The weights on the device, from the seed, in ONE jitted call, in the
    type they are served or trained in."""
    import jax

    init = resolve(config["program"]["init_params"])
    # the hardware generator: threefry takes several times as long to fill
    # gigabytes on a TPU, and every run of every check pays for it
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    fn = jax.jit(lambda k: init(cfg, k), **(
        {"out_shardings": out_shardings} if out_shardings is not None
        else {}))
    params = fn(key)
    jax.block_until_ready(params)
    return params


class Factory:
    """``model_factory`` of ``LLMServer``: pickled to the replica."""

    def __init__(self, config: dict, seed: int, rehearse: bool):
        self.config, self.seed, self.rehearse = config, seed, rehearse

    def __call__(self):
        t0 = time.perf_counter()
        shape = shape_of(self.config, self.rehearse)
        cfg = model_config(self.config, shape)
        params = init_weights(self.config, cfg, self.seed)
        SETUP["weights_s"] = time.perf_counter() - t0
        SETUP["shape"] = shape
        return params, cfg


class CompileCounter:
    """Counts programs this process compiled or loaded from the persistent
    cache (both pass ``backend_compile_duration``): whatever reaches the
    compiler after warm-up ran inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
