"""The built-in scenario catalog.

Twelve named scenarios spanning four families (see README for the table):

* ``ml-*``  — training phases synthesized from published model shapes
  (``scenarios.models``) through the DP/PP/TP collective schedule
  (``scenarios.ml``);
* ``hpc-*`` — stencil/halo and spectral BSP iteration structures;
* ``dc-*``  — stochastic datacenter arrivals (Poisson / ON-OFF / incast /
  hotspot) — the whole family shares one plan shape by construction, so it
  replays as a single stacked (scenario x policy) grid program;
* ``app-*`` — the paper's §4 application generators as catalog entries.

Default allocations are 16 nodes (runs on every topology from the 80-node
small Megafly up); ``Scenario.scaled(n)`` rescales any entry — builders
re-derive internal structure (e.g. the parallelism grid) from ``n``.

The catalog is the unit the policy auto-tuner (``repro.tuning``) consumes:
``tune_catalog`` searches the policy space per entry and hands back each
workload's energy/degradation frontier and budget winner, so every entry
here doubles as a named workload class an operator can ask
``launch.power_advisor`` about by name.
"""
from __future__ import annotations

from repro.scenarios import apps, hpc, ml, stochastic  # noqa: F401 (builders)
from repro.scenarios.registry import register_scenario
from repro.scenarios.spec import Scenario, params_of

# Display/report ordering of the scenario families (suite tables, tuner
# reports, the experiments scripts' --families flag).
FAMILIES = ("ml", "hpc", "dc", "app")

CATALOG = [
    # -- ML training (shapes from scenarios/models.py) -------------------
    Scenario(
        "ml-qwen2-1.5b", "ml", "ml_training", 16, seed=11,
        params=params_of(arch="qwen2-1.5b", iters=2),
        description="qwen2-1.5b training steps on a DP4xPP2xTP2 grid: "
                    "fused TP all-reduces, pipeline P2P, bucketed DP "
                    "gradient sync"),
    Scenario(
        "ml-gemma3-4b", "ml", "ml_training", 16, seed=12,
        params=params_of(arch="gemma3-4b", iters=2, tokens_per_iter=16384,
                         grad_buckets=6),
        description="gemma3-4b training steps, larger grads/activations "
                    "and finer gradient bucketing than ml-qwen2-1.5b"),
    Scenario(
        "ml-qwen3-moe", "ml", "moe_training", 16, seed=13,
        params=params_of(arch="qwen3-moe-30b-a3b", iters=2),
        description="qwen3-moe-30b-a3b expert-parallel training steps: "
                    "top-8 token-routing dispatch/combine all-to-alls per "
                    "fused layer block — dense symmetric bursts between "
                    "compute gaps (the dual-mode sleep-ladder stressor)"),
    # -- HPC iteration structures -----------------------------------------
    Scenario(
        "hpc-stencil3d", "hpc", "stencil_halo", 16, seed=21,
        params=params_of(dims=3, iters=12),
        description="3-D halo exchange + periodic residual all-reduce "
                    "(LAMMPS-style BSP skeleton)"),
    Scenario(
        "hpc-stencil2d", "hpc", "stencil_halo", 16, seed=22,
        params=params_of(dims=2, iters=12, halo_bytes=512 << 10,
                         compute_secs=4e-3),
        description="2-D stencil: fewer, fatter halos and a higher "
                    "compute/communication ratio"),
    Scenario(
        "hpc-spectral", "hpc", "bsp_spectral", 16, seed=23,
        params=params_of(iters=8),
        description="spectral solver: paired all-to-all transposes per "
                    "iteration — dense bursts, worst case for sleeping"),
    # -- stochastic datacenter arrivals -----------------------------------
    Scenario(
        "dc-poisson", "dc", "poisson", 16, seed=31,
        params=params_of(rate=2000.0),
        description="memoryless Poisson flows between uniform pairs, "
                    "heavy-tailed sizes"),
    Scenario(
        "dc-hotspot", "dc", "poisson", 16, seed=32,
        params=params_of(rate=2500.0, hot_frac=0.6),
        description="Poisson arrivals with 60% of flows aimed at a hot "
                    "destination set"),
    Scenario(
        "dc-onoff", "dc", "onoff", 16, seed=33,
        params=params_of(),
        description="Markov-modulated ON-OFF bursts: near-saturation "
                    "windows between near-idle ones (wake-storm regime)"),
    Scenario(
        "dc-incast", "dc", "incast", 16, seed=34,
        params=params_of(fan_in=8),
        description="partition-aggregate incast: synchronized fan-in to a "
                    "rotating aggregator over background trickle"),
    # -- paper §4 applications --------------------------------------------
    Scenario(
        "app-lammps", "app", "paper_app", 16, seed=41,
        params=params_of(app="lammps", iters=10),
        description="the paper's LAMMPS generator (halo + all-reduce "
                    "iterations, periodic FFT all-to-all)"),
    Scenario(
        "app-alexnet", "app", "paper_app", 16, seed=42,
        params=params_of(app="alexnet", iters=3),
        description="the paper's AlexNet generator (per-layer backprop "
                    "all-reduce bursts)"),
]

for _s in CATALOG:
    register_scenario(_s)
