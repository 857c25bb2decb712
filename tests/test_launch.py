"""Power advisor front doors: a catalog scenario name and a budget in, a
recommended policy and its frontier out."""
import pytest

from repro.launch.power_advisor import advise_scenario
from repro.topology.megafly import small_topology


def test_advise_scenario_recommends_within_budget():
    """The catalog front door of the auto-tuner: a scenario name + budget
    in, a budget-respecting policy recommendation + frontier out."""
    from repro.tuning import tiny_space
    tiny = small_topology(n_groups=3, leaves=2, spines=2, nodes_per_leaf=2)
    out = advise_scenario("dc-poisson", budget_pct=1.0, topo=tiny,
                          n_nodes=8, rounds=1, space=tiny_space())
    assert out["scenario"] == "dc-poisson" and out["budget_pct"] == 1.0
    assert out["row"]["exec_overhead_pct"] <= 1.0
    assert out["policy"] is not None           # a real Policy won
    assert out["recommended"] != "baseline"
    assert out["row"]["link_energy_saved_pct"] > 0.0
    names = [p["policy"] for p in out["frontier"]]
    assert out["recommended"] in names or "baseline" in names
    assert out["rounds"][0]["cells"] > 0


def test_advise_scenario_rejects_unknown_name():
    with pytest.raises(KeyError, match="unknown scenario"):
        advise_scenario("no-such-workload")
