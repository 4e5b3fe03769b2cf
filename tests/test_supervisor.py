"""Supervised crash recovery (DESIGN.md §11.4): worker kill -> restart from
artifact -> requeue with token parity; retry-budget / max-restart exhaustion
resolves every rid instead of hanging. Spawns real worker processes, so
these are the slowest serving tests (~tens of seconds on CPU)."""

import jax
import pytest

from repro.configs import build_model, get_arch, reduce_arch
from repro.core.amm import Mode
from repro.serving.artifact import save_artifact
from repro.serving.faults import FaultSpec
from repro.serving.supervisor import EngineSupervisor

ENGINE_KW = dict(n_slots=2, max_seq=64, prefill_chunk=4)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=1)
    bundle = build_model(arch, Mode.DENSE)
    params = bundle.init(jax.random.PRNGKey(0))
    path = tmp_path_factory.mktemp("sup") / "artifact"
    save_artifact(path, bundle, params)
    return path


def _specs(n=3):
    return [{"prompt": [i * 3 + 1, i * 3 + 2, i * 3 + 3], "max_tokens": 4}
            for i in range(n)]


def test_kill_restart_requeue_token_parity(artifact):
    # fault-free reference — also exercises supervisor-side cancel/timeout
    events: dict[int, list] = {}
    ref = EngineSupervisor(artifact, engine_kwargs=ENGINE_KW)
    try:
        grids = [ref.submit(s) for s in _specs()]
        g_cancel = ref.submit({"prompt": [9, 9], "max_tokens": 4})
        assert ref.cancel(g_cancel) is True       # cancelled from the outbox
        assert ref.cancel(g_cancel) is False      # already terminal
        g_late = ref.submit({"prompt": [8, 8], "max_tokens": 4,
                             "deadline_s": 1e-4})
        baseline = {g: ref.wait(g, timeout=300) for g in grids}
        assert all(st.status == "ok" for st in baseline.values())
        assert ref.wait(g_cancel, timeout=60).status == "cancelled"
        # deadline spent before the worker ever saw it: local timeout
        assert ref.wait(g_late, timeout=60).status == "timeout"
        assert ref.stats()["restarts"] == 0
    finally:
        ref.close()

    # kill the worker mid-run: restart from the artifact, requeue, replay
    sup = EngineSupervisor(
        artifact, engine_kwargs=ENGINE_KW,
        faults=FaultSpec(kill_at_step=1), retry_budget=2,
    )
    try:
        grids = []
        for s in _specs():
            g = sup.submit(s, on_event=lambda ev, _l=events.setdefault(
                len(events), []): _l.append(ev))
            grids.append(g)
        states = {g: sup.wait(g, timeout=300) for g in grids}
        stats = sup.stats()
        assert stats["restarts"] >= 1
        assert stats["requeued"] >= 1
        assert stats["lost"] == 0
        for g in grids:
            st = states[g]
            assert st.status == "ok"              # no rid silently lost
            # deterministic per-request sampling: the replayed generation is
            # byte-identical to the fault-free run
            assert st.tokens == list(baseline[g].tokens), g
        # a request that had streamed tokens before the crash told its
        # subscriber to discard them
        restart_evs = [ev for evs in events.values() for ev in evs
                       if ev[0] == "restart"]
        requeued_with_tokens = [g for g in grids if states[g].retries > 0]
        if requeued_with_tokens:
            assert restart_evs or all(
                not any(e[0] == "tokens" for e in evs) for evs in events.values()
            )
    finally:
        sup.close()


def test_crash_loop_exhausts_restarts_and_fails(artifact):
    # the fault respawns with EVERY worker incarnation: a crash loop. After
    # max_restarts consecutive deaths the supervisor fails closed — every
    # live rid resolves as "error", new submits are refused, nothing hangs.
    sup = EngineSupervisor(
        artifact, engine_kwargs=ENGINE_KW,
        faults=FaultSpec(kill_at_step=0), faults_once=False,
        retry_budget=5, max_restarts=1, healthy_after_s=3600.0,
    )
    try:
        g = sup.submit({"prompt": [1, 2, 3], "max_tokens": 4})
        st = sup.wait(g, timeout=300)
        assert st.status == "error"
        stats = sup.stats()
        assert stats["failed"] == 1
        assert not sup.healthy
        assert sup.pending() == 0
        with pytest.raises(RuntimeError, match="supervisor failed"):
            sup.submit({"prompt": [1], "max_tokens": 1})
    finally:
        sup.close()


def test_missing_artifact_fails_closed_immediately(tmp_path):
    # no crash-loop burning max_restarts against a directory that cannot be
    # served: the pre-spawn probe fails closed with an actionable error
    sup = EngineSupervisor(tmp_path / "nope", max_restarts=50)
    try:
        assert sup.wait_ready(timeout=60)         # unblocked, not hung
        assert not sup.healthy
        assert sup.stats()["spawns"] == 0         # never even spawned
        with pytest.raises(RuntimeError, match="not serveable"):
            sup.submit({"prompt": [1], "max_tokens": 1})
    finally:
        sup.close()


def test_artifact_vanishing_between_restarts_fails_closed(artifact, tmp_path):
    # the router multiplies how often the restart path runs: a worker crash
    # with the artifact gone must resolve every rid as "error" after ONE
    # failed probe, not spin through max_restarts respawn attempts
    import shutil

    copy = tmp_path / "artifact"
    shutil.copytree(artifact, copy)
    sup = EngineSupervisor(
        copy, engine_kwargs=ENGINE_KW,
        faults=FaultSpec(kill_at_step=1), max_restarts=50,
    )
    try:
        assert sup.wait_ready(timeout=300)
        g = sup.submit({"prompt": [1, 2, 3], "max_tokens": 8})
        shutil.rmtree(copy)          # gone before the injected crash restarts
        st = sup.wait(g, timeout=300)
        assert st.status == "error"
        assert not sup.healthy
        assert sup.pending() == 0
        assert sup.stats()["spawns"] == 1         # no respawn against the void
        with pytest.raises(RuntimeError, match="not serveable"):
            sup.submit({"prompt": [1], "max_tokens": 1})
    finally:
        sup.close()


def test_check_artifact_dir_probe(artifact, tmp_path):
    from repro.serving.artifact import check_artifact_dir

    manifest = check_artifact_dir(artifact)
    assert isinstance(manifest, dict)
    with pytest.raises(FileNotFoundError):
        check_artifact_dir(tmp_path / "absent")
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "manifest.json").write_text("{not json")
    with pytest.raises(ValueError, match="manifest"):
        check_artifact_dir(bad)


def test_pin_chip_gives_worker_one_chip_slice(monkeypatch):
    """A router replica's worker owns exactly one chip: a one-chip slice of
    its own, with its own runtime port, set before JAX starts."""
    from repro.serving import supervisor

    keys = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
            "TPU_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES", "TPU_PROCESS_PORT",
            "CLOUD_TPU_TASK_ID")
    for k in keys:                        # restored by monkeypatch afterwards
        monkeypatch.setenv(k, "unset")
    supervisor._pin_chip(2)
    env = {k: supervisor.os.environ[k] for k in keys}
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == f"localhost:{env['TPU_PROCESS_PORT']}"
