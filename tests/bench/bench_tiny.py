"""Small stand-ins for the benchmark's cells, for tests on the CPU.

Each cell keeps its configuration, driver, comparison and limits; only
its traffic mix is cut (fewer scenarios, 2 h horizons, 8 sites) so that
a run with a 1-2 s window fits a test.
"""
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_TRAFFIC = {
    "e9-day": dict(countries=["SE", "DE"], weather_draws=1, rhos=[0.0, 0.2],
                   event_draws=1, horizon_h=2),
    "fleet-hourly": dict(countries=["SE", "DE"], weather_draws=3, chunk=16),
    "e9-sweep": dict(countries=["SE", "DE"], weather_draws=2, rhos=[0.2],
                     event_draws=1, horizon_h=2, chunk=8),
    "ffr-storms": dict(n_sites=8, trigger_rate_per_s=40.0, storm_every_s=0.5,
                       storm_sites=4, trace_seconds=1.0),
}
# 2 h horizons see few events at 4 a day; 48 a day exercises the verdicts
TINY_ENGINE = dict(events_per_day=48.0)


# cells whose configuration, traffic and limits the benchmark keeps for a
# later PR (PERF.md, section 7): BENCHMARK.json does not name them yet
LATER = {
    "schedule-sweep": dict(config="sixgrid-engine", traffic="fleet-hourly",
                           chips=1),
    "reserve-sweep-4chip": dict(config="sixgrid-engine", traffic="e9-sweep",
                                chips=4),
}


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def with_later(find_cell):
    """``bench.run.find_cell`` that also finds the cells kept for later."""
    def find(name):
        if name not in LATER:
            return find_cell(name)
        cell = dict(name=name, **LATER[name])
        bench = _json("BENCHMARK.json")
        conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        return (bench, cell, _json(conf["file"]),
                _json("bench", "traffic", cell["traffic"] + ".json"))
    return find


def tiny_find_cell(find_cell):
    """``bench.run.find_cell`` with the cell's traffic cut to test size."""
    find_full = with_later(find_cell)

    def find(name):
        bench, cell, config, traffic = find_full(name)
        traffic = dict(traffic, **TINY_TRAFFIC[cell["traffic"]])
        if traffic["driver"] != "service":
            config = dict(config, engine=dict(config["engine"], **TINY_ENGINE))
        return bench, cell, config, traffic
    return find
