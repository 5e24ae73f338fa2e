"""Fault-injection plane + in-task fetch retry (faults/, conf
``faultInject`` / ``fetchRetryCount``):

- spec parsing: named points, ``p=``/``nth=``/``ms=`` knobs, seeded,
  typos rejected at arm time;
- determinism: the schedule is a pure function of (spec, per-point
  call index) — two injectors armed alike agree call for call;
- RetryPolicy: exponential backoff with equal jitter under a deadline
  budget anchored at the FIRST failure;
- CircuitBreaker / StripeHealth: trip → open → half-open probe →
  close, and repeated lane failures demoting striped reads;
- reader integration over loopback: transient read failures absorbed
  in-task (bit-exact result), ``fetchRetryCount=0`` restoring the
  reference first-failure conversion, breaker fast-fail, stripe
  demotion completing unstriped;
- the seeded chaos soak: loopback / tcp-threaded / tcp-async ×
  decodeThreads {0,4} × skew on/off under a mixed fault spec — every
  run is bit-exact or a clean FetchFailedError, with zero ledger
  leaks, zero double releases and zero lock-rank violations.
"""

import errno
import gc
import threading
import time
from collections import defaultdict

import pytest

from sparkrdma_tpu.conf import TpuShuffleConf
from sparkrdma_tpu.faults.breaker import CircuitBreaker, StripeHealth
from sparkrdma_tpu.faults.injector import (
    FAULTS,
    FaultInjectedError,
    FaultInjector,
    FaultSpecError,
    KNOWN_POINTS,
    parse_fault_spec,
)
from sparkrdma_tpu.faults.retry import RetryPolicy, is_transient
from sparkrdma_tpu.metrics import GLOBAL_REGISTRY
from sparkrdma_tpu.shuffle.manager import TpuShuffleManager
from sparkrdma_tpu.shuffle.partitioner import HashPartitioner
from sparkrdma_tpu.shuffle.reader import (
    FetchFailedError,
    MetadataFetchFailedError,
)
from sparkrdma_tpu.transport import LoopbackNetwork, TcpNetwork
from sparkrdma_tpu.transport.channel import (
    FatalTransportError,
    TransportError,
    decode_remote_error,
    encode_remote_error,
)
from sparkrdma_tpu.utils.dbglock import get_lock_factory
from sparkrdma_tpu.utils.ledger import get_resource_ledger
from sparkrdma_tpu.utils.statemachine import shake_confs_from_env

BASE_PORT = 42400


@pytest.fixture()
def faults_env():
    """Save/restore every process-global the fault plane touches."""
    led = get_resource_ledger()
    prev_led = led.enabled
    prev_lock = get_lock_factory().enabled
    prev_reg = GLOBAL_REGISTRY.enabled
    FAULTS.reset()
    led.reset()
    GLOBAL_REGISTRY.reset()
    yield
    FAULTS.reset()
    led.enabled = prev_led
    led.reset()
    get_lock_factory().enabled = prev_lock
    GLOBAL_REGISTRY.enabled = prev_reg
    GLOBAL_REGISTRY.reset()


def _metric_total(name):
    """Sum of one counter across all label sets."""
    return sum(
        inst.value for _k, inst in GLOBAL_REGISTRY.instruments()
        if getattr(inst, "name", "") == name
    )


# -- spec parsing -------------------------------------------------------------


def test_parse_spec_points_knobs_and_seed():
    seed, clauses = parse_fault_spec(
        "connect:p=0.1;read_resp:p=0.05;serve_delay:ms=30;"
        "lane_kill:nth=7;seed=42"
    )
    assert seed == 42
    assert set(clauses) == {"connect", "read_resp", "serve_delay",
                            "lane_kill"}
    assert clauses["connect"].p == 0.1
    assert clauses["serve_delay"].ms == 30
    assert clauses["lane_kill"].nth == 7
    # empty/whitespace specs arm nothing
    assert parse_fault_spec("") == (0, {})
    assert parse_fault_spec(" ; ") == (0, {})


@pytest.mark.parametrize("bad", [
    "frobnicate:p=0.5",          # unknown point
    "connect",                   # no knobs
    "connect:p",                 # not key=value
    "connect:q=1",               # unknown key
    "connect:p=1.5",             # p out of range
    "connect:p=banana",          # unparsable
    "connect:nth=0",             # nth must be >= 1
    "serve_delay:ms=-3",         # negative delay
    "seed=xyz",                  # bad seed
])
def test_parse_spec_rejects(bad):
    with pytest.raises(FaultSpecError):
        parse_fault_spec(bad)


def test_every_known_point_parses():
    spec = ";".join(f"{p}:nth=3" for p in KNOWN_POINTS)
    _seed, clauses = parse_fault_spec(spec)
    assert set(clauses) == set(KNOWN_POINTS)


# -- determinism --------------------------------------------------------------


def test_probability_schedule_is_deterministic():
    spec = "recv:p=0.3;seed=17"
    a, b = FaultInjector(), FaultInjector()
    a.arm(spec)
    b.arm(spec)
    assert [a.fires("recv") for _ in range(300)] == \
           [b.fires("recv") for _ in range(300)]
    assert a.fired_counts() == b.fired_counts()
    assert 0 < a.fired_counts()["recv"] < 300


def test_nth_schedule_fires_on_exact_multiples():
    inj = FaultInjector()
    inj.arm("send:nth=4")
    hits = [inj.fires("send") for _ in range(12)]
    assert hits == [False, False, False, True] * 3


def test_points_draw_independent_streams():
    """Interleaving calls to another point must not perturb a point's
    own schedule (per-point rng + counter)."""
    spec = "recv:p=0.5;send:p=0.5;seed=9"
    solo, mixed = FaultInjector(), FaultInjector()
    solo.arm(spec)
    mixed.arm(spec)
    want = [solo.fires("recv") for _ in range(100)]
    got = []
    for _ in range(100):
        mixed.fires("send")
        got.append(mixed.fires("recv"))
    assert got == want


def test_ms_clause_sleeps_instead_of_raising():
    inj = FaultInjector()
    inj.arm("serve_delay:ms=20")
    t0 = time.monotonic()
    inj.check("serve_delay")    # must NOT raise
    assert time.monotonic() - t0 >= 0.015
    assert inj.fired_counts() == {"serve_delay": 1}


def test_check_raises_transient_fault():
    inj = FaultInjector()
    inj.arm("recv:nth=1")
    with pytest.raises(FaultInjectedError) as ei:
        inj.check("recv")
    assert ei.value.point == "recv"
    assert is_transient(ei.value)


def test_owner_counting_keeps_schedule_until_last_stop():
    inj = FaultInjector()
    inj.arm("recv:nth=2;seed=1")
    inj.arm("recv:nth=2;seed=1")    # second manager, same spec
    assert inj.enabled
    assert [inj.fires("recv") for _ in range(4)] == \
           [False, True, False, True]
    inj.stop()
    assert inj.enabled              # one owner still armed
    # re-arming kept the LIVE schedule: counters carried on above
    inj.stop()
    assert not inj.enabled
    assert not inj.fires("recv")    # disarmed: nothing fires


def test_unarmed_point_never_fires():
    inj = FaultInjector()
    inj.arm("recv:nth=1")
    assert not inj.fires("connect")
    inj.check("connect")            # no clause: returns silently


# -- retry policy -------------------------------------------------------------


def test_retry_policy_disabled_at_count_zero():
    rp = RetryPolicy(0, 50, 10_000)
    assert not rp.enabled
    assert rp.next_delay_ms(1, 0) is None


def test_retry_backoff_doubles_with_equal_jitter():
    import random as _random
    rp = RetryPolicy(5, 100, 60_000, rng=_random.Random(7))
    for attempts in (1, 2, 3, 4, 5):
        base = 100 * 2 ** (attempts - 1)
        for _ in range(20):
            d = rp.next_delay_ms(attempts, 0)
            assert base / 2 <= d <= base, (attempts, d)
    assert rp.next_delay_ms(6, 0) is None     # attempts exhausted
    assert rp.next_delay_ms(0, 0) is None     # not a failure count


def test_retry_deadline_budget():
    rp = RetryPolicy(10, 1000, 500)
    assert rp.next_delay_ms(1, 500) is None   # budget gone
    assert rp.next_delay_ms(1, 501) is None
    d = rp.next_delay_ms(1, 400)              # clamped to what's left
    assert d is not None and d <= 100


# -- breaker + stripe health --------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_breaker_trips_half_opens_and_recovers():
    clk = _Clock()
    br = CircuitBreaker(failures=3, reset_ms=2_000, name="p", clock=clk)
    assert br.state == "closed"
    for _ in range(2):
        br.record_failure()
    assert br.allow() and br.state == "closed"
    br.record_failure()                       # third strike
    assert br.state == "open" and br.trips == 1
    assert not br.allow()
    clk.t += 1.0
    assert not br.allow()                     # still inside reset_ms
    clk.t += 1.5
    assert br.allow()                         # the half-open probe
    assert br.state == "half-open"
    assert not br.allow()                     # probe already out
    br.record_success()
    assert br.state == "closed" and br.allow()


def test_breaker_halfopen_failure_reopens_and_restarts_clock():
    clk = _Clock()
    br = CircuitBreaker(failures=1, reset_ms=1_000, name="p", clock=clk)
    br.record_failure()
    assert br.state == "open"
    clk.t += 1.0
    assert br.allow()                         # probe admitted
    br.record_failure()                       # probe failed
    assert br.state == "open"
    clk.t += 0.5
    assert not br.allow()                     # clock restarted
    clk.t += 0.5
    assert br.allow()


def test_breaker_disabled_at_failures_zero():
    br = CircuitBreaker(failures=0, reset_ms=1_000)
    for _ in range(50):
        br.record_failure()
    assert br.allow() and br.trips == 0


def test_stripe_health_demotes_and_expires():
    clk = _Clock()
    sh = StripeHealth(failures=2, demote_ms=5_000, name="p", clock=clk)
    sh.note_lane_failure()
    assert not sh.demoted()
    sh.note_lane_failure()
    assert sh.demoted()
    clk.t += 4.9
    assert sh.demoted()
    clk.t += 0.2
    assert not sh.demoted()                   # window expired
    # a success while healthy clears accumulated strikes
    sh.note_lane_failure()
    sh.note_success()
    sh.note_lane_failure()
    assert not sh.demoted()


def test_stripe_health_disabled_at_failures_zero():
    sh = StripeHealth(failures=0, demote_ms=5_000)
    for _ in range(10):
        sh.note_lane_failure()
    assert not sh.demoted()


# -- error classification -----------------------------------------------------------


def test_transient_classification_and_wire_roundtrip():
    assert is_transient(TransportError("blip"))
    assert not is_transient(FatalTransportError("gone"))
    assert not is_transient(ValueError("nope"))
    # fatal survives the status!=0 reason string; transient stays plain
    wire = encode_remote_error(FatalTransportError("no block store"))
    assert wire.startswith("FATAL:")
    back = decode_remote_error(wire)
    assert isinstance(back, FatalTransportError)
    assert not is_transient(back)
    plain = decode_remote_error(encode_remote_error(TransportError("x")))
    assert is_transient(plain)


# -- reader integration over loopback -----------------------------------------


def _loop_cluster(extra, driver_port, n_exec=2):
    net = LoopbackNetwork()
    d = {
        "spark.shuffle.tpu.driverPort": driver_port,
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "10s",
        "spark.shuffle.tpu.metrics": True,
    }
    d.update(extra)
    conf = TpuShuffleConf(d)
    driver = TpuShuffleManager(conf, is_driver=True, network=net)
    executors = [
        TpuShuffleManager(
            conf, is_driver=False, network=net,
            port=driver_port + 100 + i * 10, executor_id=str(i),
        )
        for i in range(n_exec)
    ]
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if all(len(e._peers) == n_exec for e in executors):
            break
        time.sleep(0.01)
    return net, conf, driver, executors


def _write_maps(driver, executors, sid, num_maps=2, num_parts=4,
                rows=200, vbytes=600):
    """Deterministic records; returns (handle, maps_by_host, expected)."""
    part = HashPartitioner(num_parts)
    handle = driver.register_shuffle(sid, num_maps, part)
    expected = defaultdict(list)
    maps_by_host = defaultdict(list)
    for m in range(num_maps):
        recs = [
            (f"s{sid}m{m}r{j}", bytes([(m + j) % 251]) * vbytes)
            for j in range(rows)
        ]
        for k, v in recs:
            expected[k].append(v)
        ex = executors[m % len(executors)]
        w = ex.get_writer(handle, m)
        w.write(recs)
        w.stop(True)
        maps_by_host[ex.local_smid].append(m)
    return handle, dict(maps_by_host), expected


def _read_all(reader, expected):
    got = defaultdict(list)
    for k, v in reader.read():
        got[k].append(bytes(v) if not isinstance(v, bytes) else v)
    assert set(got) == set(expected)
    for k in expected:
        assert sorted(got[k]) == sorted(expected[k]), k


def test_reader_absorbs_transient_read_faults_bit_exact(faults_env):
    """Every second read response is cut; with in-task retries the
    read completes BIT-EXACT and the retry counters moved."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "read_resp:nth=2;seed=3",
        "spark.shuffle.tpu.fetchRetryCount": 10,
        "spark.shuffle.tpu.fetchRetryWaitMs": "2ms",
        "spark.shuffle.tpu.fetchRetryMaxMs": "30s",
    }, BASE_PORT, n_exec=3)
    try:
        # three hosts -> two remote fetch groups: the nth=2 schedule
        # cuts the second group's response, the retry lands it
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0, num_maps=3)
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        _read_all(reader, expected)
        fired = FAULTS.fired_counts()
        assert fired.get("read_resp", 0) > 0, fired
        assert _metric_total("shuffle_fetch_retries_total") > 0
        assert _metric_total("fault_injected_total") > 0
    finally:
        for m in executors + [driver]:
            m.stop()


def test_retry_disabled_converts_first_failure(faults_env):
    """fetchRetryCount=0: the reference posture — the FIRST transport
    failure converts to FetchFailedError, no retries, no recording."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "read_resp:nth=1",
        "spark.shuffle.tpu.fetchRetryCount": 0,
    }, BASE_PORT + 60)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0)
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        with pytest.raises(FetchFailedError):
            for _ in reader.read():
                pass
        assert _metric_total("shuffle_fetch_retries_total") == 0
        assert _metric_total("transport_breaker_trips_total") == 0
    finally:
        for m in executors + [driver]:
            m.stop()


def test_breaker_trips_on_persistent_peer_failure(faults_env):
    """Every read response fails: strikes trip the per-peer breaker,
    the fetch converts cleanly, and the trip is counted."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "read_resp:nth=1",
        "spark.shuffle.tpu.fetchRetryCount": 3,
        "spark.shuffle.tpu.fetchRetryWaitMs": "1ms",
        "spark.shuffle.tpu.fetchBreakerFailures": 2,
        "spark.shuffle.tpu.fetchBreakerResetMs": "60s",
    }, BASE_PORT + 120)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0)
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        with pytest.raises(FetchFailedError):
            for _ in reader.read():
                pass
        assert _metric_total("transport_breaker_trips_total") >= 1
        assert _metric_total("shuffle_fetch_failures_total") >= 1
    finally:
        for m in executors + [driver]:
            m.stop()


def test_fresh_reader_probes_open_breaker_after_heal(faults_env):
    """The breaker is node-resident and outlives the task — but a
    stage retry's FRESH reader must not be fast-failed on stale state
    when the peer healed: its first fetch per peer goes out as the
    probe, succeeds, and closes the breaker (the lineage contract:
    heal + re-register + rerun must complete)."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "read_resp:nth=1",
        "spark.shuffle.tpu.fetchRetryCount": 2,
        "spark.shuffle.tpu.fetchRetryWaitMs": "1ms",
        "spark.shuffle.tpu.fetchBreakerFailures": 2,
        # far past the test: only the probe path can get through
        "spark.shuffle.tpu.fetchBreakerResetMs": "600s",
    }, BASE_PORT + 140)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0)
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        with pytest.raises(FetchFailedError):
            for _ in reader.read():
                pass
        assert _metric_total("transport_breaker_trips_total") >= 1
        # the peer heals (fault plane disarmed) and the stage retries:
        # a new shuffle, a new reader, the same open breaker
        FAULTS.reset()
        handle2, maps2, expected2 = _write_maps(
            driver, executors, 2)
        reader2 = executors[0].get_reader(handle2, 0, 4, maps2)
        _read_all(reader2, expected2)
        # the successful probe closed it: a third read sails through
        reader3 = executors[0].get_reader(handle2, 0, 4, maps2)
        _read_all(reader3, expected2)
    finally:
        for m in executors + [driver]:
            m.stop()


def test_striped_lane_kill_demotes_to_unstriped(faults_env):
    """Lane kills fail the striped attempt; health demotes the peer to
    the unstriped small-read lane and the retry completes bit-exact
    (the degradation ladder: striped -> unstriped -> FetchFailed)."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "lane_kill:nth=2;seed=5",
        "spark.shuffle.tpu.fetchRetryCount": 8,
        "spark.shuffle.tpu.fetchRetryWaitMs": "2ms",
        "spark.shuffle.tpu.fetchRetryMaxMs": "30s",
        "spark.shuffle.tpu.transportNumStripes": 2,
        # the threshold clamps at its 64k floor: blocks must beat THAT
        "spark.shuffle.tpu.transportStripeThreshold": "64k",
        "spark.shuffle.tpu.stripeDemoteFailures": 1,
        "spark.shuffle.tpu.stripeDemoteMs": "60s",
        "spark.shuffle.tpu.fetchBreakerFailures": 0,
    }, BASE_PORT + 180)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0, rows=240, vbytes=1500)
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        _read_all(reader, expected)
        fired = FAULTS.fired_counts()
        assert fired.get("lane_kill", 0) >= 1, fired
        assert _metric_total("transport_stripe_demotions_total") >= 1
    finally:
        for m in executors + [driver]:
            m.stop()


def test_late_stripe_progress_release_races_settle_clean(
        faults_env, monkeypatch):
    """Regression (found by the shaken tcp-async chaos soak): the
    reader's per-stripe progress callback claims its n bytes under the
    pending lock but releases the window ticket AFTER dropping it,
    while settle() used to close the ticket with a no-arg release — a
    settle overtaking that claim->release window turned the late
    release(n) into a DoubleReleaseError.  settle() now releases the
    explicit remainder, so the releases sum exactly in any order.

    The interleaving is forced deterministically: every group read
    fires one injected progress report from a side thread, the ticket
    release under it parks on an event inside the claim->release
    window, and only then does the completion (and thus settle) run."""
    from sparkrdma_tpu.transport import stripe as stripe_mod
    from sparkrdma_tpu.transport.channel import (
        FnCompletionListener as FnCL,
    )
    from sparkrdma_tpu.utils import ledger as ledger_mod

    parked = threading.Event()
    orig_release = ledger_mod.ResourceTicket.release

    def parking_release(self, amount=None):
        if self.resource == "reader.inflight_bytes" and amount:
            parked.set()  # the claim happened; now park in the window
            time.sleep(0.05)
        return orig_release(self, amount)

    monkeypatch.setattr(
        ledger_mod.ResourceTicket, "release", parking_release)

    orig_rb = stripe_mod.ReadGroup.read_blocks

    def racing_rb(self, locations, listener, on_progress=None,
                  tenant=None, ctx=None):
        if on_progress is None:
            return orig_rb(self, locations, listener, tenant=tenant,
                           ctx=ctx)
        total = sum(loc.length for loc in locations)
        racer = threading.Thread(target=on_progress, args=(total // 2,))

        def on_success(blocks):
            parked.clear()
            racer.start()
            # wait until the progress claim is parked inside its
            # claim->release window, THEN let completion settle
            assert parked.wait(5), "progress release never parked"
            listener.on_success(blocks)
            racer.join(10)

        # the real per-stripe progress stays suppressed (on_progress
        # None below) — the injected racer is the only window release
        # besides settle, so the arithmetic stays exact
        return orig_rb(self, locations, FnCL(on_success,
                                             listener.on_failure),
                       tenant=tenant, ctx=ctx)

    monkeypatch.setattr(stripe_mod.ReadGroup, "read_blocks", racing_rb)

    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.resourceDebug": True,
        "spark.shuffle.tpu.transportNumStripes": 2,
        "spark.shuffle.tpu.transportStripeThreshold": "64k",
    }, BASE_PORT + 340)
    ledger = get_resource_ledger()
    assert ledger.enabled
    got = defaultdict(list)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0, rows=240, vbytes=1500)
        for pid in range(4):
            rd = executors[pid % 2].get_reader(
                handle, pid, pid + 1, dict(maps_by_host))
            for k, v in rd.read():
                got[k].append(bytes(v) if not isinstance(v, bytes) else v)
    finally:
        for m in executors + [driver]:
            m.stop()
    assert set(got) == set(expected)
    for k in expected:
        assert sorted(got[k]) == sorted(expected[k]), k
    assert ledger.double_releases() == 0, ledger.leak_report()


def test_location_rpc_fault_is_a_clean_metadata_failure(faults_env):
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "location_rpc:nth=1",
    }, BASE_PORT + 240)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0)
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        with pytest.raises(MetadataFetchFailedError):
            for _ in reader.read():
                pass
    finally:
        for m in executors + [driver]:
            m.stop()


def test_dropped_publish_fails_clean_not_wrong(faults_env):
    """Every publish run is 'lost': the reader must time out with a
    clean metadata failure (stage retry), never a wrong answer — and
    the drop re-marked the runs dirty for the next publish."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "publish:nth=1",
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "2s",
    }, BASE_PORT + 300)
    try:
        handle, maps_by_host, expected = _write_maps(
            driver, executors, 0, rows=20, vbytes=64)
        assert FAULTS.fired_counts().get("publish", 0) >= 1
        reader = executors[0].get_reader(handle, 0, 4, maps_by_host)
        with pytest.raises(MetadataFetchFailedError):
            for _ in reader.read():
                pass
    finally:
        for m in executors + [driver]:
            m.stop()


def test_dropped_heartbeats_do_not_prune_live_executors(faults_env):
    """Probe drops model lost packets: acks from the surviving probes
    keep last_ack fresh, so nobody is pruned."""
    net, conf, driver, executors = _loop_cluster({
        "spark.shuffle.tpu.faultInject": "heartbeat:nth=2",
        "spark.shuffle.tpu.heartbeatInterval": "100ms",
        "spark.shuffle.tpu.heartbeatTimeout": "2s",
    }, BASE_PORT + 360)
    try:
        time.sleep(0.8)
        assert len(driver.executors) == 2
        assert FAULTS.fired_counts().get("heartbeat", 0) >= 1
    finally:
        for m in executors + [driver]:
            m.stop()


def test_accept_paths_survive_transient_errors(faults_env):
    """ECONNABORTED from accept() (a peer that reset mid-handshake —
    routine when an injected connect fault kills a client) must not
    take the LISTENER down: that would refuse every future peer on
    the node forever.  Only listener-is-gone errnos are fatal."""
    GLOBAL_REGISTRY.enabled = True  # fixture restores

    class _Disp:
        def __init__(self):
            self.unregistered = []

        def sel_register(self, *a):
            pass

        def sel_unregister(self, s):
            self.unregistered.append(s)

    class _Srv:
        def __init__(self, errs):
            self._errs = list(errs)

        def fileno(self):
            return 99

        def accept(self):
            raise self._errs.pop(0)

        def close(self):
            pass

    from sparkrdma_tpu.transport.dispatcher import Acceptor

    d = _Disp()
    acc = Acceptor(d, None, _Srv([OSError(errno.ECONNABORTED, "aborted"),
                                  OSError(errno.EMFILE, "fd pressure")]))
    acc.on_readable()  # transient: listener survives
    acc.on_readable()
    assert not acc._closed and not d.unregistered
    acc_dead = Acceptor(d, None, _Srv([OSError(errno.EBADF, "closed")]))
    acc_dead.on_readable()  # fatal: unregisters and closes
    assert acc_dead._closed and len(d.unregistered) == 1

    # the threaded analog: survives the abort, returns on EBADF
    net = TcpNetwork()
    srv = _Srv([OSError(errno.ECONNABORTED, "aborted"),
                OSError(errno.EBADF, "closed")])
    net._accept_forever(srv, None)
    assert not srv._errs  # consumed the abort, returned on EBADF
    assert _metric_total("transport_accept_transient_errors_total") >= 3


# -- the seeded chaos soak ----------------------------------------------------

_SOAK_SPEC = (
    "connect:p=0.04;read_resp:p=0.06;serve_delay:ms=2,p=0.3;"
    "lane_kill:nth=9;stripe:p=0.03;send:p=0.015;disk_read:p=0.04;"
    "heartbeat:p=0.2;seed={seed}"
)


def _soak_shuffle(driver, executors, sid, outcomes, errors):
    """One shuffle under chaos: record 'exact' or 'failed-clean'."""
    try:
        # per-partition blocks beat the 64k stripe-threshold floor, so
        # the lane_kill/stripe points actually see striped traffic
        handle, maps_by_host, expected = _write_maps(
            driver, executors, sid, rows=160, vbytes=2000)
        try:
            reader = executors[sid % len(executors)].get_reader(
                handle, 0, 4, maps_by_host)
            _read_all(reader, expected)
            outcomes.append("exact")
        except FetchFailedError:
            # clean, stage-retriable — the allowed degraded outcome
            outcomes.append("failed-clean")
        finally:
            driver.unregister_shuffle(sid)
    except BaseException as e:  # anything else is a soak failure
        errors.append(e)


@pytest.mark.parametrize("transport", ["loopback", "tcp-threaded",
                                       "tcp-async"])
@pytest.mark.parametrize("decode_threads", [0, 4])
@pytest.mark.parametrize("skew", [False, True])
def test_chaos_soak_exact_or_clean_zero_leaks(
        faults_env, transport, decode_threads, skew):
    """The acceptance soak: a mixed seeded fault spec over the full
    engine matrix, under resourceDebug + lockDebug.  Contract: every
    shuffle is bit-exact or a clean FetchFailedError — never a hang,
    wrong answer, ledger leak, double release or rank violation."""
    get_lock_factory().enabled = False
    idx = (["loopback", "tcp-threaded", "tcp-async"].index(transport) * 4
           + decode_threads // 4 * 2 + int(skew))
    driver_port = BASE_PORT + 500 + idx * 60
    extra = {
        "spark.shuffle.tpu.faultInject": _SOAK_SPEC.format(seed=100 + idx),
        "spark.shuffle.tpu.resourceDebug": True,
        "spark.shuffle.tpu.lockDebug": True,
        "spark.shuffle.tpu.metrics": True,
        "spark.shuffle.tpu.fetchRetryCount": 4,
        "spark.shuffle.tpu.fetchRetryWaitMs": "5ms",
        "spark.shuffle.tpu.fetchRetryMaxMs": "3s",
        "spark.shuffle.tpu.decodeThreads": decode_threads,
        "spark.shuffle.tpu.skewEnabled": skew,
        "spark.shuffle.tpu.transportNumStripes": 2,
        "spark.shuffle.tpu.transportStripeThreshold": "64k",
        "spark.shuffle.tpu.tierHotBytes": "64k",  # force disk reads
        "spark.shuffle.tpu.driverPort": driver_port,
        "spark.shuffle.tpu.partitionLocationFetchTimeout": "8s",
        "spark.shuffle.tpu.connectTimeout": "5s",
    }
    # make chaos-shake: SCHED_SHAKE=<seed> layers the deterministic
    # schedule shaker + state validator onto the same soak
    extra.update(shake_confs_from_env())
    if transport != "loopback":
        extra["spark.shuffle.tpu.transportAsyncDispatcher"] = (
            transport == "tcp-async")

    def mk_conf():
        return TpuShuffleConf(dict(extra))

    if transport == "loopback":
        net = LoopbackNetwork()
        driver = TpuShuffleManager(
            mk_conf(), is_driver=True, network=net)
        executors = [
            TpuShuffleManager(
                mk_conf(), is_driver=False, network=net,
                port=driver_port + 100 + i * 10, executor_id=str(i),
            )
            for i in range(2)
        ]
    else:
        driver = TpuShuffleManager(
            mk_conf(), is_driver=True, network=TcpNetwork(),
            port=driver_port, stage_to_device=False,
        )
        # the test ports sit inside the kernel's ephemeral range, so a
        # leaked outgoing connection from an earlier test can occupy
        # driver_port and _bind_node moves the driver up a port —
        # executors must dial the port it ACTUALLY bound (the
        # conf-broadcast analog), not the one we asked for
        extra["spark.shuffle.tpu.driverPort"] = driver.node.address[1]
        executors = [
            TpuShuffleManager(
                mk_conf(), is_driver=False, network=TcpNetwork(),
                port=driver_port + 100 + i * 10, executor_id=str(i),
                stage_to_device=False,
            )
            for i in range(2)
        ]
    ledger = get_resource_ledger()
    assert ledger.enabled  # the conf flipped it on
    outcomes: list = []
    errors: list = []
    try:
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            if all(len(e._peers) == 2 for e in executors):
                break
            time.sleep(0.01)
        threads = [
            threading.Thread(
                target=_soak_shuffle,
                args=(driver, executors, sid, outcomes, errors),
            )
            for sid in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive(), "chaos soak hung"
        assert not errors, errors
        assert len(outcomes) == 2 and set(outcomes) <= {
            "exact", "failed-clean"}, outcomes

        # idle now: every TASK-lifetime resource must drain.  Open
        # sockets (tcp.fds) are CONNECTION-lifetime — legitimately
        # held while the cluster is up; the managers' own stops below
        # audit those via resource_leaked_total.
        gc.collect()
        deadline = time.monotonic() + 10
        left = {}
        while time.monotonic() < deadline:
            left = {r: n for r, n in ledger.outstanding().items()
                    if n and r != "tcp.fds"}
            if not left:
                break
            time.sleep(0.05)
        assert not left, (left, ledger.leak_report())
        assert ledger.double_releases() == 0, ledger.leak_report()
        assert FAULTS.fired_counts(), "the chaos spec never fired"
    finally:
        for m in executors + [driver]:
            m.stop()
    # the last manager's stop flushed the ledger epoch: nothing —
    # including the sockets — survived teardown
    leaked = [
        (dict(inst.labels), inst.value)
        for _k, inst in GLOBAL_REGISTRY.instruments()
        if getattr(inst, "name", "") == "resource_leaked_total"
        and inst.value > 0
    ]
    assert not leaked, leaked
    viol = [
        inst for _k, inst in GLOBAL_REGISTRY.instruments()
        if getattr(inst, "name", "") == "lock_rank_violations_total"
    ]
    assert all(v.value == 0 for v in viol), [v.value for v in viol]
    doubles = [
        inst.value for _k, inst in GLOBAL_REGISTRY.instruments()
        if getattr(inst, "name", "") == "resource_double_release_total"
    ]
    assert all(v == 0 for v in doubles), doubles
    # under stateDebug/schedShake (make chaos-shake) every lifecycle
    # transition was validated against its declared table: zero
    # illegal-transition attempts allowed anywhere in the soak
    illegal = [
        (dict(inst.labels), inst.value)
        for _k, inst in GLOBAL_REGISTRY.instruments()
        if getattr(inst, "name", "") == "state_transitions_illegal_total"
        and inst.value > 0
    ]
    assert not illegal, illegal
