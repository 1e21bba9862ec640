"""What the tests of this directory share beyond ``tests/conftest.py``.

**The sites' account of the real step, on the lowering the census pays
for.** ``test_benchmark_census.py::test_the_programs_step_fills_every_role``
lowers every configuration's grad step at its cell's batch and real widths,
once, about a minute each. The fixture below rides that one lowering: it
gives the test a compile counter and an empty record of the call sites
before, and holds the counter's ``by_site`` to what the lowered step's own
kernel counts say after (PR 54: every Mosaic call site times its own
tracing, ``ops/pallas/lowering.py``). No second lowering, and no edit to
the census's file."""
import pytest

#: configuration -> site -> (traced calls, keys) of one trace of the real
#: step, where the census's kernel counts fix them: ``keyevl2``'s seven
#: layers call the indexer's three kernels in the forward rule, in its
#: replay and in the backward rule, on one key each (21 of each kernel in
#: the lowered step)
SITES_OF_THE_REAL_STEP = {
    "keyevl2": {"indexer selection": (21, 1), "indexer scores": (21, 1),
                "indexer alignment": (21, 1), "selected attention": (7, 1)},
}
RIDES = "test_the_programs_step_fills_every_role"


@pytest.fixture(autouse=True)
def sites_account_of_the_census_lowering(request):
    if getattr(request.node, "originalname", None) != RIDES:
        yield
        return
    from dalle_tpu.obs import compiles
    from dalle_tpu.ops.pallas import lowering
    record, lowering._RECORD = lowering._RECORD, {}
    counter = compiles.install(None)
    try:
        yield
        by_site = counter.snapshot()["by_site"]
    finally:
        lowering._RECORD = record
    config = request.node.callspec.params["config"]
    assert by_site, f"{config}: no call site timed its tracing"
    for site, at in by_site.items():
        assert at["calls"] >= at["keys"] >= 1, (config, site, at)
        assert at["again_n"] == at["calls"] - at["keys"], (config, site, at)
        assert at["trace_s"] >= at["again_s"] >= 0, (config, site, at)
    for site, (calls, keys) in SITES_OF_THE_REAL_STEP.get(config,
                                                          {}).items():
        assert (by_site[site]["calls"], by_site[site]["keys"]) == (
            calls, keys), (config, site, by_site[site])
