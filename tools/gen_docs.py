"""Generate docs/CONFIGURATION.md and docs/SENSORS.md from the live code.

The reference documents its ~250 config keys and ~40 JMX sensors in its
wiki (docs/wiki/User Guide); here the configuration reference is derived
from the ConfigDef registry itself so it can never drift, and the sensor
list from the names the code actually exports plus the /metrics gauges.

Run: python tools/gen_docs.py   (CI could diff-check the output)
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def gen_configuration() -> str:
    from cruise_control_tpu.config.cruise_control_config import _DEFINITION

    d = _DEFINITION
    lines = [
        "# Configuration reference",
        "",
        "Generated from the ConfigDef registry by `tools/gen_docs.py` — "
        "do not edit by hand.",
        "",
        f"{len(list(d.names))} keys. Reference parity: "
        "`config/constants/*Config.java` (~250 keys) merged by "
        "`KafkaCruiseControlConfig.java`.",
        "",
    ]
    by_importance: dict[str, list] = {}
    for name in sorted(d.names):
        key = d.key(name)
        by_importance.setdefault(key.importance.name, []).append(key)
    for importance in ("HIGH", "MEDIUM", "LOW"):
        keys = by_importance.get(importance, [])
        if not keys:
            continue
        lines += [f"## {importance.title()} importance", "",
                  "| Key | Type | Default | Description |", "|---|---|---|---|"]
        for key in keys:
            default = key.default if key.has_default else "(required)"
            if isinstance(default, list):
                default = ", ".join(str(x).rsplit(".", 1)[-1]
                                    for x in default) or "[]"
            doc = " ".join(str(key.doc).split())
            lines.append(f"| `{key.name}` | {key.type.name} | "
                         f"`{default}` | {doc} |")
        lines.append("")
    return "\n".join(lines)


def gen_sensors() -> str:
    """Every sensor name the code records, discovered by scanning for
    SENSORS.count/gauge/record_timer call sites, plus the /metrics extra
    gauges assembled in api/server.py."""
    pattern = re.compile(
        r"SENSORS\.(count|gauge|record_timer|observe|set_counter|set_timer)\("
        r"\s*[\"']([a-z0-9_]+)[\"']")
    extra = re.compile(r"extra\[[\"']([a-z0-9_]+)[\"']\]")
    found: dict[str, tuple[str, str]] = {}
    for path in sorted((ROOT / "cruise_control_tpu").rglob("*.py")):
        text = path.read_text()
        for kind, name in pattern.findall(text):
            found[name] = (kind, str(path.relative_to(ROOT)))
        for name in extra.findall(text):
            found[name] = ("gauge", str(path.relative_to(ROOT)))
    lines = [
        "# Sensors",
        "",
        "Generated by `tools/gen_docs.py` — do not edit by hand.",
        "",
        "Exported at `/metrics` in Prometheus exposition format under the "
        "`kafka_cruisecontrol_` prefix (the JMX sensor surface of the "
        "reference's `docs/wiki/User Guide/Sensors.md`, domain "
        "`kafka.cruisecontrol`). Timers export `_seconds_total`/`_count`/"
        "`_last_seconds`; counters export `_total`; histograms export "
        "cumulative `_bucket{le=...}` + `_sum`/`_count` (log-spaced "
        "bounds). The span tracer (utils/tracing.py) additionally exports "
        "`trace_span_seconds{span=...}` — one histogram series per span "
        "name — on every span close.",
        "",
        "| Sensor | Kind | Recorded by |", "|---|---|---|",
    ]
    for name in sorted(found):
        kind, where = found[name]
        kind = {"count": "counter", "gauge": "gauge",
                "record_timer": "timer", "observe": "histogram",
                "set_counter": "counter", "set_timer": "timer"}[kind]
        lines.append(f"| `kafka_cruisecontrol_{name}` | {kind} | `{where}` |")
    lines.append("")
    return "\n".join(lines)


def main() -> None:
    docs = ROOT / "docs"
    docs.mkdir(exist_ok=True)
    (docs / "CONFIGURATION.md").write_text(gen_configuration())
    (docs / "SENSORS.md").write_text(gen_sensors())
    print("wrote docs/CONFIGURATION.md and docs/SENSORS.md")


if __name__ == "__main__":
    main()
