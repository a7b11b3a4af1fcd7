# lint-as: examples/fixture.py
# RPR003: out-of-src code must use the GraphSpec -> plan -> generate front
# door, not the internal per-model executors / stream drivers.
from repro import api
from repro.core import PBAConfig
from repro.core.pba import generate_pba  # expect: RPR003
from repro.core.stream import PBAStream as Stream  # expect: RPR003
import repro.core.stream as stream_mod


def bad_calls(cfg, table):
    edges, stats = generate_pba(cfg, table)  # expect: RPR003
    drv = Stream(cfg, table)  # expect: RPR003
    stream_mod.stream_to_shards(drv, "/tmp/out")  # expect: RPR003
    return edges, stats


def suppressed(cfg, table):
    return generate_pba(cfg, table)  # spmdlint: disable=RPR003


def good():
    spec = api.preset("paper_smoke")
    return api.generate(api.plan(spec))
