"""Every catalog scenario under every policy kind, replayed through the
batched (scenario x policy) grid, against the host step-loop oracle.

``test_scenarios.test_grid_matches_step_loop_reference`` holds two dc
scenarios under two policies to the oracle; this file holds all twelve
catalog entries (8 nodes on the 12-node Megafly) under all nine kinds."""
import functools

import numpy as np
import pytest

from repro import scenarios as SC
from repro.core import simulator as S
from repro.core.eee import PowerModel
from repro.core.sweep import sweep_scenarios
from repro.topology.megafly import small_topology
from test_policy_contract import ALL_KINDS, _policy

PM = PowerModel()
TINY = small_topology(n_groups=3, leaves=2, spines=2, nodes_per_leaf=2)
POLICIES = {kind: _policy(kind) for kind in ALL_KINDS}


def _trace(name):
    return SC.build_trace(SC.get_scenario(name).scaled(8), TINY)


@functools.lru_cache(maxsize=None)
def _grid(name):
    """One batched sweep of the scenario under all nine kinds."""
    return sweep_scenarios({name: _trace(name)}, TINY, POLICIES, PM)[name]


@pytest.mark.parametrize("kind", ALL_KINDS)
@pytest.mark.parametrize("name", list(SC.catalog()))
def test_catalog_grid_matches_reference(name, kind):
    want, _ = S.simulate_trace_reference(_trace(name), TINY, POLICIES[kind],
                                         PM)
    got, want = _grid(name)[kind].as_dict(), want.as_dict()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-9, atol=1e-12,
                                   err_msg=f"{name}/{kind}.{k}")
