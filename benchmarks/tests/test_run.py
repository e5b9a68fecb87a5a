"""The command, rehearsed at 16 brokers / 512 partitions on the CPU: the
last line holds the contract's keys; without a chip and without
``--rehearse`` it exits non-zero and prints no result; the control, every
planted fault and a run whose timed path is broken underneath come out as
not correct."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH, TINY


def command(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark", TINY,
         *args], capture_output=True, text=True, env=env, timeout=600)


@pytest.mark.parametrize("workload,traced,expected", [
    ("tiny.rebalance", 0, {"proposal_s", "balancedness_after", "setup_s"}),
    ("tiny.reads-behind-solve", 0, {"proposal_s", "read_p95_ms", "setup_s"}),
    ("tiny.rebalance", 1, {"round.rounds_per_proposal",
                           "pump.dispatches_per_proposal",
                           "host.outside_solver_ms", "xla.compile_s"}),
])
def test_rehearsal_prints_the_contracts_line(workload, traced, expected):
    done = command("--workload", workload, "--seed", str(2**31 + 11),
                   "--seconds", "2", "--trace", str(traced), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # a rehearsal is stamped cpu and prints no device metric
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert set(result["metrics"]) == expected
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert all(value <= limit
               for value, limit in result["compared"].values())
    last = done.stderr.strip().splitlines()
    assert last[-1] == "correct: True"
    assert last[-2].startswith("compared: ")


def test_no_chip_no_result():
    done = command("--workload", "tiny.rebalance", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "the cell needs 1 TPU chip" in done.stderr
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]


def test_unknown_workload_no_result():
    done = command("--workload", "nope", "--seed", "1", "--seconds", "1",
                   "--trace", "0", "--rehearse")
    assert done.returncode != 0 and "no workload" in done.stderr


@pytest.fixture(scope="module")
def sound(tiny, cpu_device):
    import run
    return run.run_cell(tiny, "tiny.reads-behind-solve", 5, 1.5, False,
                        cpu_device, time.monotonic(), faults=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert all(v == 0 for v, _limit in sound["compared"].values())


def test_every_planted_fault_reads_over_its_limit(sound):
    from benchlib.faults import READ_FAULTS, planted
    for fault, number in {**planted("proposals"), **READ_FAULTS}.items():
        reading = sound["faulted"][fault.__name__][number]
        limit = sound["compared"][number][1]
        assert reading > limit, fault.__name__


@pytest.mark.parametrize("fault", ["no_moves", "half_moves", "dead_broker",
                                   "stale_read"])
def test_a_broken_timed_path_is_not_correct(tiny, cpu_device, monkeypatch,
                                            fault):
    """The rest of a run, past the look for a chip, with the answers
    altered where the client takes them off the socket: a step that returns
    its state unchanged, half of the batch left out, an answer altered."""
    import run
    from benchlib import faults, traffic
    from benchlib.deployment import build
    with open(os.path.join(BENCH, "tests", "tiny-16b-512p.json")) as f:
        dep = build(json.load(f))
    broken, sound_call = getattr(faults, fault), traffic.http_call

    def http_call(port, method, endpoint, *args, **kwargs):
        status, body = sound_call(port, method, endpoint, *args, **kwargs)
        if status == 200 and body and "proposals" in body \
                and fault != "stale_read":
            body = {**body, "proposals": broken(body["proposals"], dep)}
        elif status == 200 and body and endpoint == "load" \
                and fault == "stale_read":
            body = broken(body, dep)
        return status, body

    monkeypatch.setattr(traffic, "http_call", http_call)
    result = run.run_cell(tiny, "tiny.reads-behind-solve", 8, 1.5, False,
                          cpu_device, time.monotonic())
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("control,number", [
    ("no_hard_goals", "rack_violations"), ("rack_only", "over_capacity")])
def test_the_control_is_not_correct(tiny, cpu_device, control, number):
    import run
    with open(os.path.join(BENCH, "tests", "tiny-16b-512p.json")) as f:
        patch = json.load(f)["controls"][control]["patch"]
    result = run.run_cell(tiny, "tiny.rebalance", 6, 1.0, False, cpu_device,
                          time.monotonic(), cfg_patch=patch)
    assert result["correct"] is False
    assert result["compared"][number][0] > 0


@pytest.mark.parametrize("operation,brokers", [
    ("rebalance", []), ("remove_broker", [3, 7])])
def test_operations_are_data(tiny, cpu_device, operation, brokers):
    """A deployment's operation and its brokers are keys of its file."""
    import run
    result = run.run_cell(
        tiny, "tiny.rebalance", 7, 1.0, False, cpu_device, time.monotonic(),
        cfg_patch={"operation": operation, "operation_brokers": brokers})
    assert result["correct"] is True, result["compared"]
    assert result["workload"]["proposals"] > 0


def test_add_broker_is_held_to_its_operations_rule(tiny, cpu_device):
    """``guarantees/add_broker.py`` is found by the operation's name and
    its count printed beside the limit 0. What the program answers (whether
    it is ``correct``, how many moves, what the count reads) is not held
    here: today's program places replicas on old brokers, a faithful one
    may not, and the PR that repairs it cannot edit this file. The start is
    Kafka's own (``placement: kafka_rack_aware``), which a faithful
    program can scale out; the skewed start it may have to refuse."""
    import run
    result = run.run_cell(
        tiny, "tiny.rebalance", 7, 1.0, False, cpu_device, time.monotonic(),
        cfg_patch={"operation": "add_broker", "operation_brokers": [14, 15],
                   "placement": "kafka_rack_aware"})
    reading, limit = result["compared"]["onto_old_broker"]
    assert limit == 0 and reading >= 0
    assert result["correct"] is all(
        v <= lim for v, lim in result["compared"].values())
