"""What the surfaces share: the state every surface starts from, the
sample of questions a check draws from the run's seed, the check itself
and its float32 control."""
from __future__ import annotations

import contextlib

import numpy as np


def base_state(config: dict, traffic: dict) -> dict:
    """The configuration's fabric and power model, in the program and in
    the frozen reference."""
    from refsim import topology as ref_topology
    from repro.core.eee import PowerModel
    from repro.topology.fattree import FatTree
    from repro.topology.megafly import Megafly
    spec = config["topology"]
    kinds = {"megafly": Megafly, "fattree": FatTree}
    return {"config": config, "traffic": traffic,
            "topo": kinds[spec["kind"]](**spec["params"]),
            "ref_topo": ref_topology.build(spec),
            "pm": PowerModel(**config["power_model"])}


@contextlib.contextmanager
def on_cpu():
    """Place the reference's arrays and programs on the host's CPU."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        yield


def sample(questions: list, seed: int, k: int) -> list:
    """``k`` of the completed questions, drawn from ``seed``, in order."""
    rng = np.random.default_rng(seed % (1 << 63))
    idx = rng.choice(len(questions), size=min(k, len(questions)),
                     replace=False)
    return [questions[i] for i in sorted(idx)]


def check(surface, state: dict, questions: list, seed: int) -> list:
    """Each compared number of the sampled questions, at its worst, beside
    its limit.  The reference runs on the host's CPU."""
    limits = state["traffic"]["check"]["limits"]
    worst = {k: 0 for k in limits}
    picked = sample(questions, seed, state["traffic"]["check"]["questions"])
    if not picked:
        worst["missing"] = 1
    with on_cpu():
        for q in picked:
            gaps = surface.compare(surface.answer(q),
                                   surface.reference(state, q["seed"]))
            for k, v in gaps.items():
                worst[k] = max(worst[k], v)
    return [{"name": k, "value": worst[k], "limit": limits[k]}
            for k in limits]


def control(surface, state: dict, seed: int) -> dict:
    """The compared numbers of the control: the reference in float32, in
    the program's place on the default device, against the reference in
    float64 on the CPU."""
    import warnings

    import jax
    with on_cpu():
        want = surface.reference(state, seed)
    with warnings.catch_warnings(), jax.enable_x64(False):
        warnings.simplefilter("ignore")
        got = surface.reference(state, seed)
    return surface.compare(got, want)
