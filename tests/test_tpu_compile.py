"""Compiles of the main path's device programs for a described TPU v5e.

Nothing runs: each test lowers a program at the size the chip runs it and
compiles it with the TPU compiler for a chip that is described, not
attached — so a kernel that breaks the tiling rules, or an op the TPU
compiler refuses (64-bit integer dots), fails here instead of on the chip.
The topology is described inside a fixture (never at import), and the
persistent compile cache is off around these compiles: their entries
could not be read back without a chip.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.apps import multicamera  # noqa: E402
from repro.core.architecture import paper_architecture  # noqa: E402
from repro.core.dse import GenotypeSpace  # noqa: E402

MRB_SHAPE = dict(B=4, C=4096, kv=8, G=12, d=128)  # benchmarks/mrb_kernel.py


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_of(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return shape


@pytest.fixture(scope="module")
def mcam_tables():
    """Decode tables of Multicamera with every multicast actor replaced by
    its MRB (the ξ = 1 pattern), plus the genotype width."""
    from repro.evo.decode import DecodeTables
    from repro.evo.encoding import PopulationLayout

    space = GenotypeSpace(multicamera(), paper_architecture())
    layout = PopulationLayout(space, "explore")
    return DecodeTables(space, (1,) * layout.n_xi), layout.n_genes


@pytest.mark.parametrize(
    "objectives",
    [("period", "memory", "core_cost"), ("sim_period", "memory", "core_cost")],
)
def test_relaxed_decode_compiles_at_population_512(shape_of, mcam_tables, objectives):
    from repro.evo.decode import make_relaxed_eval

    tables, n_genes = mcam_tables
    with jax.enable_x64(True):
        fn = jax.jit(make_relaxed_eval(tables, objectives))
        compiled = fn.lower(shape_of((512, n_genes), jnp.int32)).compile()
    assert compiled.as_text()


def test_ranking_compiles_at_768(shape_of):
    from repro.evo.ranking import crowding, domination_matrix, nondomination_ranks

    with jax.enable_x64(True):  # the relaxed loop ranks float64 objectives
        F = shape_of((768, 3), jnp.float64)
        jax.jit(nondomination_ranks).lower(F).compile()
        jax.jit(crowding).lower(F, shape_of((768,), jnp.int32)).compile()
    # the exact path ranks int32 order keys
    jax.jit(domination_matrix).lower(shape_of((768, 3), jnp.int32)).compile()


def _sim_operands(shape_of, static, batch):
    A, C, H, Tmax = (static[k] for k in ("A", "C", "H", "Tmax"))
    return (
        shape_of((batch, A, Tmax, 1 + H), jnp.int32),
        shape_of((batch, A, A), jnp.bool_),
        shape_of((batch, C), jnp.int32),
        shape_of((), jnp.int32),
    )


def test_vectorized_simulator_compiles_at_batch_64(shape_of, mcam_tables):
    from repro.sim.model import SimConfig
    from repro.sim.vectorized import _build_sim

    static = mcam_tables[0].static
    fn = _build_sim(static, SimConfig(), 16, donate=False)
    fn.lower(*_sim_operands(shape_of, static, 64)).compile()


def _while_body_shapes(hlo: str):
    """Result shapes of the instructions in every ``while`` body of an HLO
    module's text, as ``(name, dims)``."""
    shapes = []
    for body in re.findall(r"while\(.*?body=%?([\w.\-]+)", hlo):
        start = hlo.index(f"%{body} ")
        text = hlo[start:hlo.index("\n}\n", start)]
        for name, dims in re.findall(r"^\s*(?:ROOT )?%?(\S+) = \(?\w+\[([\d,]*)\]", text, re.M):
            shapes.append((name, tuple(int(d) for d in dims.split(",") if d)))
    return shapes


@pytest.mark.parametrize("xi", [0, 1])
def test_simulator_round_selects_no_one_hot_task_row(shape_of, xi):
    """The Multicamera simulator at batch 64: no op in the round loop
    outputs a row of the one-hot task table (minor dimension 2+C+R) — each
    actor's current task is selected as a few packed int32 words."""
    from repro.evo.decode import DecodeTables
    from repro.evo.encoding import PopulationLayout
    from repro.sim.model import SimConfig
    from repro.sim.vectorized import _build_sim

    space = GenotypeSpace(multicamera(), paper_architecture())
    layout = PopulationLayout(space, "explore")
    static = DecodeTables(space, (xi,) * layout.n_xi).static
    fn = _build_sim(static, SimConfig(), 32, donate=False)
    hlo = fn.lower(*_sim_operands(shape_of, static, 64)).compile().as_text()
    shapes = _while_body_shapes(hlo)
    assert len(shapes) > 20, "no while body found"
    row = 2 + static["C"] + static["R"]
    assert [(n, d) for n, d in shapes if d and d[-1] == row] == []


def test_sim_step_kernel_round_body_is_refused(shape_of, mcam_tables):
    """The Pallas simulator's blocks pass the tiling rule, but Mosaic
    refuses the shared round body — the reason ``sim_backend="auto"``
    routes TPU batches to ``vectorized``.  When this stops raising, route
    TPU back to the kernel and measure it."""
    from repro.kernels.sim_step import build_pallas_sim

    static = mcam_tables[0].static
    fn = build_pallas_sim(static, None, 16, interpret=False)
    with pytest.raises(Exception, match="unsupported shape cast"):
        fn.lower(*_sim_operands(shape_of, static, 64)).compile()


def test_mrb_append_compiles(shape_of):
    from repro.kernels.mrb_ring import mrb_append

    B, C, kv, d = (MRB_SHAPE[k] for k in ("B", "C", "kv", "d"))
    fn = jax.jit(lambda b, o, t: mrb_append(b, o, t, interpret=False))
    compiled = fn.lower(
        shape_of((B, C, kv, d), jnp.bfloat16),
        shape_of((), jnp.int32),
        shape_of((B, 1, kv, d), jnp.bfloat16),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mrb_decode_attention_compiles(shape_of):
    from repro.kernels.decode_attention import mrb_decode_attention

    B, C, kv, G, d = (MRB_SHAPE[k] for k in ("B", "C", "kv", "G", "d"))
    fn = jax.jit(lambda q, k, v, t: mrb_decode_attention(q, k, v, t, interpret=False))
    kv_shape = shape_of((B, C, kv, d), jnp.bfloat16)
    compiled = fn.lower(
        shape_of((B, kv * G, d), jnp.bfloat16), kv_shape, kv_shape,
        shape_of((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_generation_step_compiles(shape_of, mcam_tables):
    """The whole relaxed generation (rank → vary → decode → simulate →
    rank → truncate) at population 512 / 256 offspring."""
    from repro.evo.encoding import PopulationLayout
    from repro.evo.explorer import JaxNSGA2Explorer

    space = GenotypeSpace(multicamera(), paper_architecture())
    layout = PopulationLayout(space, "always")
    G = layout.n_genes
    forced = np.zeros(G, bool)
    forced[layout.xi_slice] = True
    explorer = JaxNSGA2Explorer(population=512, offspring=256, evaluation="relaxed")
    objectives = ("sim_period", "memory", "core_cost")
    with jax.enable_x64(True):
        step = explorer._fused_step(
            space, (1,) * layout.n_xi, True, objectives, layout.bounds,
            ~forced, forced, forced.astype(np.int32),
        )
        step.lower(
            shape_of((2,), jnp.uint32),
            shape_of((512, G), jnp.int32),
            shape_of((512, len(objectives)), jnp.float64),
        ).compile()
