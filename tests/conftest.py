"""Test environment: force an 8-device virtual CPU mesh.

Multi-device behavior (sharding, collectives, psum-before-push, one device
per PS server) is tested on one host by faking 8 CPU devices, mirroring how
the reference tests multi-node via N processes over loopback ZMQ
(SURVEY.md §4).  The environment must be set before jax is imported.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# every test run compiles from cold, here and in the processes tests spawn:
# entry points turn the persistent compile cache on (utils/platform.py), and
# a test must not pass or fail by what an earlier run left in it
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On any failing ``chaos``/``migration``-marked test, print the seeds.

    Seeded chaos runs are deterministic given (seed, send order), so a CI
    failure should be a one-liner to reproduce locally — but only if the
    seed makes it into the failure output.  Parametrized seeds come from
    ``item.callspec``; tests with hardcoded seeds can instead stash one via
    ``item.user_properties.append(("chaos_seed", seed))``.  Migration /
    rebalance tests (PR 6) get the same one-line repro contract — their
    kill-mid-stream and skew scenarios are seed-driven the same way, as do
    the durability-plane ``checkpoint`` drills (PR 16: kill-mid-snapshot,
    torn-file, reshard-restore) and the ``consistency``-plane gate drills
    (PR 20: SSP bound under seeded chaos, restart, migration).
    """
    outcome = yield
    report = outcome.get_result()
    if report.when != "call" or not report.failed:
        return
    if (
        "chaos" not in item.keywords
        and "migration" not in item.keywords
        and "checkpoint" not in item.keywords
        and "consistency" not in item.keywords
    ):
        return
    seeds = {}
    params = getattr(item, "callspec", None)
    if params is not None:
        for name, value in params.params.items():
            if "seed" in name.lower():
                seeds[name] = value
    for name, value in item.user_properties:
        if "seed" in name.lower():
            seeds[name] = value
    repro = f"pytest '{item.nodeid}'"
    detail = (
        f"chaos seeds: {seeds}" if seeds
        else "chaos seeds: (none recorded — check the test's literals)"
    )
    report.sections.append(
        ("chaos repro", f"{detail}\nrepro: {repro}")
    )
    # Black-box postmortem: the process-wide flight recorder still holds the
    # last N transport/KV events of the failed scenario — capture them before
    # the next test overwrites the ring.  Best-effort: a broken recorder must
    # not turn one failure into two.
    try:
        import pathlib
        import re

        from parameter_server_tpu.core import flightrec

        slug = re.sub(r"[^A-Za-z0-9_.-]+", "_", item.nodeid)[-80:]
        out_dir = pathlib.Path("/tmp/ps_postmortem") / slug
        paths = flightrec.dump(str(out_dir), reason=f"test-failure:{item.nodeid}")
        if paths:
            report.sections.append(
                (
                    "postmortem bundle",
                    "\n".join(paths)
                    + f"\nmerge: python tools/postmortem.py {out_dir}/*.json",
                )
            )
    except Exception:
        pass
