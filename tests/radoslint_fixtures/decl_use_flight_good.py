"""Known-negative decl-use: the flight-recorder / metrics-history
pattern — an option family applied through a prefix-slicing observer
(utils/flight.py, mgr_history_* in mgr/daemon.py) and per-kernel
bandwidth gauges set through an f-string name (offload/service.py) —
all live uses the lint's prefix-const heuristic must honor."""

_DEFAULTS = {"enabled": True, "capacity": 512}


def FLIGHT_OPTIONS(Option):
    return [Option("flight_enabled", "bool", _DEFAULTS["enabled"],
                   "applied via the observer below"),
            Option("flight_ring_capacity", "int", _DEFAULTS["capacity"],
                   "applied via the observer below")]


def register_config(config, Option, recorder):
    names = []
    for opt in FLIGHT_OPTIONS(Option):
        names.append(opt.name)
        config.declare(opt)

    def _on_change(name, value):
        key = name[len("flight_"):]
        if key in _DEFAULTS:
            _DEFAULTS[key] = value
        setattr(recorder, key, value)

    config.add_observer(tuple(names), _on_change)


def declare_roofline(perf):
    for kind in ("enc", "dec"):
        perf.add(f"kernel_{kind}_gbps",
                 description="EWMA achieved bandwidth")


def note_kernel(perf, kind, gbps):
    perf.set(f"kernel_{kind}_gbps", round(gbps, 4))
