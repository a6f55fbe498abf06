"""tldiag (tensorlink_tpu/diag.py): cluster health table, manifest diffing,
and the end-to-end acceptance scenario — kill a worker mid-job and watch
the black box light up on every surviving node."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.config import NodeConfig
from tensorlink_tpu.diag import (
    cluster_table,
    main,
    node_row,
    render_table,
    scrape_cluster,
    scrape_node,
)


def test_node_row_self_healed_replaces_low_accept():
    """A node whose engine already downgraded its own speculation
    (serving.py _maybe_self_heal) renders SELF-HEALED(mode), not
    LOW-ACCEPT — the flag's condition cleared without an operator."""
    def scrape(serving):
        return {
            "target": "s:1",
            "routes": {
                "/healthz": {"status": 200, "body": {"ok": True}},
                "/node": {"status": 200, "body": {
                    "role": "user", "node_id": "u" * 64, "peers": {},
                    "serving": serving,
                }},
            },
        }

    low_spec = {
        "mode": "draft", "proposed_total": 500, "acceptance_rate": 0.1,
    }
    advisory = node_row(scrape({"spec": low_spec}), 10.0, 2.0)
    assert any(f.startswith("LOW-ACCEPT") for f in advisory["flags"])
    healed = node_row(scrape({
        "spec": dict(low_spec, mode="ngram"),
        "spec_self_healed": {"from": "draft", "to": "ngram",
                             "acceptance": 0.1},
    }), 10.0, 2.0)
    assert "SELF-HEALED(ngram)" in healed["flags"]
    assert not any(f.startswith("LOW-ACCEPT") for f in healed["flags"])
    # healed all the way out of speculation: no spec stats at all, the
    # record alone still tells the operator what happened
    off = node_row(scrape({
        "spec_self_healed": {"from": "ngram", "to": "nonspec",
                             "acceptance": 0.05},
    }), 10.0, 2.0)
    assert "SELF-HEALED(nonspec)" in off["flags"]
    text = render_table([healed, off])
    assert "SELF-HEALED" in text


def test_sparkline_and_check_render():
    """tldiag watch/check primitives: sparklines scale into the 8-step
    block ramp, and render_check emits GitHub workflow commands with
    one ::error per firing SLO alert."""
    from tensorlink_tpu.diag import render_check, sparkline

    s = sparkline([0.0, 1.0], width=32)
    assert s[0] == "▁" and s[-1] == "█"
    assert sparkline([], width=8) == ""
    assert len(sparkline(list(range(100)), width=16)) == 16

    alert = {
        "name": "ttft-burn:interactive", "severity": "error",
        "rule": "ttft-burn:interactive", "detail": "0.9 > 0.1",
    }
    result = {
        "targets": ["h:1"],
        "nodes": {"h:1": {"alerts": [alert]}},
        "firing": [{**alert, "target": "h:1"}],
        "ok": False,
    }
    gh = render_check(result, "github")
    assert "::error" in gh and "ttft-burn:interactive" in gh
    txt = render_check(result, "text")
    assert "FAIL" in txt
    ok = render_check(
        {"targets": ["h:1"], "nodes": {}, "firing": [], "ok": True},
        "github",
    )
    assert "::notice" in ok and "::error" not in ok


def test_node_row_flags_shedding():
    """A node whose serving admission stats show a RECENT shed renders
    SHEDDING(total); an old shed total with no recent activity is
    history, not a flag."""
    def scrape(admission):
        return {
            "target": "s:1",
            "routes": {
                "/healthz": {"status": 200, "body": {"ok": True}},
                "/node": {"status": 200, "body": {
                    "role": "user", "node_id": "u" * 64, "peers": {},
                    "serving": {"admission": admission},
                }},
            },
        }

    hot = node_row(scrape({
        "shed_total": 17, "retry_after_s": 0.4, "last_shed_age_s": 2.5,
        "shed_by_priority": {"batch": 15, "standard": 2},
    }), 10.0, 2.0)
    assert "SHEDDING(17)" in hot["flags"]
    calm = node_row(scrape({
        "shed_total": 17, "retry_after_s": 0.01,
        "last_shed_age_s": 3600.0,
    }), 10.0, 2.0)
    assert not any(f.startswith("SHEDDING") for f in calm["flags"])
    never = node_row(scrape({"shed_total": 0, "retry_after_s": 0.01}),
                     10.0, 2.0)
    assert not any(f.startswith("SHEDDING") for f in never["flags"])
    assert "SHEDDING" in render_table([hot])


def test_node_row_flags_kv_pool_pressure():
    """A serving node whose /node reports a paged KV pool near capacity
    is flagged KV-PRESSURE (admissions about to backpressure); a calm
    pool only fills the KV% column."""
    hot = node_row({
        "target": "s:1",
        "routes": {
            "/healthz": {"status": 200, "body": {"ok": True}},
            "/node": {"status": 200, "body": {
                "role": "user", "node_id": "u" * 64, "peers": {},
                "serving": {"pool": {
                    "num_blocks": 100, "blocks_in_use": 95,
                    "utilization": 0.95,
                }},
            }},
        },
    })
    assert hot["kv_pool_pct"] == 95.0
    assert "KV-PRESSURE(95/100)" in hot["flags"]
    calm = node_row({
        "target": "s:2",
        "routes": {
            "/healthz": {"status": 200, "body": {"ok": True}},
            "/node": {"status": 200, "body": {
                "role": "user", "node_id": "u" * 64, "peers": {},
                "serving": {"pool": {
                    "num_blocks": 100, "blocks_in_use": 10,
                    "utilization": 0.10,
                }},
            }},
        },
    })
    assert calm["kv_pool_pct"] == 10.0 and calm["flags"] == []
    text = render_table([hot, calm])
    assert "KV%" in text and "KV-PRESSURE" in text


def _manifest(programs):
    return {"programs": programs, "suppress": []}


def test_manifest_diff_directions():
    """tlhlo manifest keys: memory/collective bytes are lower-better at
    the threshold; alias/donated are EXACT with shrinkage = regression
    (a dropped donation); added/removed programs always reported."""
    from tensorlink_tpu.diag import manifest_diff, render_manifest_diff

    old = _manifest({
        "continuous.decode": {
            "group": "continuous", "dtype": "bfloat16", "donated": 12,
            "alias": 12, "collectives": {}, "f32_dot": 0,
            "f32_convert": 24, "host_calls": 0, "temp_bytes": 300_000,
            "argument_bytes": 120_000, "output_bytes": 66_000,
        },
        "infer.kv_shard_decode": {
            "group": "infer", "dtype": "bfloat16", "donated": 0,
            "alias": 0, "collectives": {"all-gather": 4096},
            "f32_dot": 0, "f32_convert": 48, "host_calls": 0,
            "temp_bytes": 1_000_000, "argument_bytes": 500_000,
            "output_bytes": 1_000,
        },
        "trainer.step": {"alias": 109, "donated": 109,
                         "temp_bytes": 50_000},
    })
    new = _manifest({
        "continuous.decode": {
            **old["programs"]["continuous.decode"],
            "alias": 10,              # two donations dropped: regression
            "temp_bytes": 400_000,    # scratch grew >5%: regression
        },
        "infer.kv_shard_decode": {
            **old["programs"]["infer.kv_shard_decode"],
            "collectives": {"all-gather": 2048},  # halved: improvement
            "f32_convert": 40,                    # fewer upcasts: improvement
        },
        "paged.decode": {"alias": 14, "donated": 14, "temp_bytes": 1},
    })
    d = manifest_diff(old, new, threshold=0.05)
    assert "continuous.decode.alias" in d["regressions"]
    assert "continuous.decode.temp_bytes" in d["regressions"]
    assert "infer.kv_shard_decode.collectives.all-gather" in d["improvements"]
    assert "infer.kv_shard_decode.f32_convert" in d["improvements"]
    assert d["added"] == ["paged.decode"]
    assert d["removed"] == ["trainer.step"]
    # exact keys carry no delta_frac; byte keys do
    rec = d["programs"]["continuous.decode"]["alias"]
    assert rec["regression"] is True and "delta_frac" not in rec
    assert d["programs"]["continuous.decode"]["temp_bytes"][
        "delta_frac"
    ] == pytest.approx(1 / 3, abs=1e-3)
    text = render_manifest_diff(d)
    assert "REGRESSION continuous.decode alias: 12 -> 10" in text
    assert "improved   infer.kv_shard_decode collectives.all-gather" in text
    assert "added      paged.decode" in text
    assert "removed    trainer.step" in text


def test_manifest_diff_new_collective_kind_regresses():
    from tensorlink_tpu.diag import manifest_diff

    old = _manifest({"p": {"collectives": {}, "temp_bytes": 10}})
    new = _manifest({
        "p": {"collectives": {"all-reduce": 64}, "temp_bytes": 10},
    })
    d = manifest_diff(old, new)
    assert d["regressions"] == ["p.collectives.all-reduce"]
    # and the kind DISAPPEARING is an improvement, not a crash
    d = manifest_diff(new, old)
    assert d["improvements"] == ["p.collectives.all-reduce"]


def test_manifest_diff_growth_from_zero_pin_regresses():
    """f32_dot/host_calls/temp_bytes going 0 -> N is the highest-signal
    move those keys make — a relative threshold can't see it, so it
    must be an unconditional regression verdict."""
    from tensorlink_tpu.diag import manifest_diff

    old = _manifest({"p": {"f32_dot": 0, "host_calls": 0,
                           "temp_bytes": 0}})
    new = _manifest({"p": {"f32_dot": 5, "host_calls": 1,
                           "temp_bytes": 4096}})
    d = manifest_diff(old, new)
    assert sorted(d["regressions"]) == [
        "p.f32_dot", "p.host_calls", "p.temp_bytes",
    ]
    # and back to zero is the mirror improvement, never a regression
    back = manifest_diff(new, old)
    assert back["regressions"] == []
    assert sorted(back["improvements"]) == [
        "p.f32_dot", "p.host_calls", "p.temp_bytes",
    ]


def test_manifest_diff_dtype_flip_is_a_verdict():
    """dtype is a string (invisible to the numeric flatten) but a
    bfloat16->float32 flip switches TLH103 off for that program — the
    diff must never render it as zero change."""
    from tensorlink_tpu.diag import manifest_diff, render_manifest_diff

    old = _manifest({"p": {"dtype": "bfloat16", "temp_bytes": 10}})
    new = _manifest({"p": {"dtype": "float32", "temp_bytes": 10}})
    d = manifest_diff(old, new)
    assert d["regressions"] == ["p.dtype"]
    assert "REGRESSION p dtype: bfloat16 -> float32" in (
        render_manifest_diff(d)
    )


def test_cli_manifest_diff(tmp_path, capsys):
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(_manifest(
        {"continuous.decode": {"alias": 12, "donated": 12,
                               "temp_bytes": 100}}
    )))
    b.write_text(json.dumps(_manifest(
        {"continuous.decode": {"alias": 12, "donated": 12,
                               "temp_bytes": 90}}
    )))
    assert main(["manifest-diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "improved   continuous.decode temp_bytes" in out
    assert main(["manifest-diff", str(a), str(b), "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["improvements"] == ["continuous.decode.temp_bytes"]


def test_node_row_flags_synthetic():
    dead = node_row({"target": "x:1", "error": "refused"})
    assert dead["flags"] == ["DEAD"] and dead["healthy"] is None
    sick = node_row({
        "target": "x:2",
        "routes": {
            "/healthz": {"status": 503, "body": {
                "ok": False, "reasons": {"watchdog:job_step": "stalled"},
            }},
            "/node": {"status": 200, "body": {
                "role": "user", "node_id": "u" * 64,
                "peers": {"w" * 16: {"last_seen_age_s": 99.0}},
                "stragglers": {"skew": 3.0, "slowest_stage": 1},
            }},
            "/metrics": {"status": 200, "body": {
                "counters": {"train_nonfinite_total": 2},
            }},
            "/events": {"status": 200, "body": {"events": [
                {"kind": "watchdog_trip", "severity": "error"},
            ]}},
        },
    }, stale_heartbeat_s=30.0)
    assert "UNHEALTHY" in sick["flags"]
    assert "STALE-HEARTBEAT" in sick["flags"]
    assert any(f.startswith("STRAGGLER") for f in sick["flags"])
    assert "ANOMALIES" in sick["flags"]
    assert sick["anomalies"] == {"train_nonfinite_total": 2}
    assert sick["error_events"] == 1
    text = render_table([dead, sick])
    assert "watchdog:job_step" in text  # reasons surfaced under the table


# ----------------------------------------------------------- live scrape


@pytest.mark.asyncio
async def test_scrape_live_node_routes():
    from tensorlink_tpu.roles.worker import WorkerNode

    node = WorkerNode(NodeConfig(role="worker", host="127.0.0.1", port=0,
                                 http_status_port=0))
    await node.start()
    try:
        node.metrics.incr("steps")  # empty registries export no prom lines
        scrape = await scrape_node(f"127.0.0.1:{node._http.bound_port}")
        assert "error" not in scrape
        assert scrape["routes"]["/healthz"]["status"] == 200
        assert scrape["routes"]["/node"]["body"]["node_id"] == node.node_id
        assert "traceEvents" in scrape["routes"]["/spans"]["body"]
        assert scrape["routes"]["/events"]["body"]["events"]
        assert "tensorlink" in scrape["routes"]["/metrics?format=prom"]["text"]
        row = node_row(scrape)
        assert row["healthy"] is True and row["flags"] == []
    finally:
        await node.stop()


# ------------------------------------------------------------ acceptance


@pytest.mark.asyncio
async def test_worker_death_flips_health_events_and_tldiag_table():
    """ISSUE 4 acceptance: kill a worker mid-job. The user AND validator
    /healthz flip unhealthy with reasons, /events carries the peer-drop
    and watchdog events, and a tldiag bundle's cluster table flags the
    dead node."""
    from tensorlink_tpu.models.mlp import MLP, MLPConfig
    from tensorlink_tpu.roles.registry import InMemoryRegistry
    from tensorlink_tpu.roles.user import UserNode
    from tensorlink_tpu.roles.validator import ValidatorNode
    from tensorlink_tpu.roles.worker import WorkerNode

    def cfg(role, **kw):
        return NodeConfig(role=role, host="127.0.0.1", port=0,
                          http_status_port=0, health_interval_s=0.1, **kw)

    reg = InMemoryRegistry()
    validator = ValidatorNode(cfg("validator"), registry=reg)
    await validator.start()
    workers = []
    for _ in range(2):
        w = WorkerNode(cfg("worker"))
        await w.start()
        await w.connect("127.0.0.1", validator.port)
        workers.append(w)
    user = UserNode(cfg("user", step_watchdog_s=0.6))
    await user.start()
    v_peer = await user.connect("127.0.0.1", validator.port)

    m = MLP(MLPConfig(in_dim=16, hidden_dim=32, out_dim=4, num_layers=2))
    p = m.init(jax.random.key(0))
    victim = None
    try:
        job = await user.request_job(
            m.seq, p["seq"], v_peer,
            max_stage_bytes=16 * 32 * 4 + 200,  # -> 2 stages, no spare
            micro_batches=2,
            train={"optimizer": "sgd", "learning_rate": 0.05},
        )
        assert user.flight.events(kind="job_placed")
        assert validator.flight.events(kind="job_accepted")

        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 16)).astype(np.float32)
        w_true = rng.normal(size=(16, 4))
        y = np.argmax(x @ w_true, -1)

        def loss_grad(logits, micro):
            lj = jnp.asarray(logits)
            yj = jnp.asarray(np.array_split(y, 2)[micro])

            def f(logit):
                logz = jax.nn.logsumexp(logit, axis=-1)
                ll = jnp.take_along_axis(logit, yj[:, None], axis=-1)[..., 0]
                return jnp.mean(logz - ll)

            val, g = jax.value_and_grad(f)(lj)
            return float(val), np.asarray(g)

        await job.train_step(x, loss_grad)  # arms + kicks the step dog
        st, _ = await _healthz(user)
        assert st == 200

        # ---- kill the stage-1 worker mid-job (no spare to recruit)
        victim_id = job.stages[1].peer.node_id
        victim = next(w for w in workers if w.node_id == victim_id)
        victim_http = victim._http.bound_port
        await victim.stop()
        await asyncio.sleep(0.3)  # EOF -> on_peer_lost on user+validator

        # the next step cannot recover (no replacement worker): it fails,
        # and from then on no step completes -> the step watchdog trips
        with pytest.raises((RuntimeError, ConnectionError)):
            await job.train_step(x, loss_grad)
        await asyncio.sleep(1.0)

        # ---- user /healthz: 503 with the stage condition + watchdog
        st, body = await _healthz(user)
        assert st == 503 and body["ok"] is False
        jid = job.job.job_id[:16]
        assert any(
            k.startswith(f"condition:job:{jid}:stage1") for k in body["reasons"]
        ), body["reasons"]
        assert f"watchdog:job_step:{jid}" in body["watchdogs"] or any(
            k.startswith("watchdog:job_step") for k in body["reasons"]
        )

        # ---- validator /healthz: 503, its placed worker is gone
        st, body = await _healthz(validator)
        assert st == 503 and any(
            k.startswith("condition:job:") for k in body["reasons"]
        )

        # ---- /events on the user: peer-drop + watchdog + lifecycle
        kinds = {e["kind"] for e in user.flight.events()}
        assert {"peer_lost", "stage_peer_lost", "watchdog_trip",
                "job_placed", "step_retry"} <= kinds, kinds
        assert {"placed_worker_lost", "job_accepted"} <= {
            e["kind"] for e in validator.flight.events()
        }

        # ---- tldiag: scrape the cluster (dead node's port included)
        survivor = next(w for w in workers if w.node_id != victim_id)
        targets = [
            f"127.0.0.1:{user._http.bound_port}",
            f"127.0.0.1:{validator._http.bound_port}",
            f"127.0.0.1:{survivor._http.bound_port}",
            f"127.0.0.1:{victim_http}",
        ]
        bundle = await scrape_cluster(targets, timeout=3.0)
        assert bundle["targets"] == targets
        rows = cluster_table(bundle)
        by_target = {r["target"]: r for r in rows}
        assert "DEAD" in by_target[f"127.0.0.1:{victim_http}"]["flags"]
        assert "UNHEALTHY" in by_target[f"127.0.0.1:{user._http.bound_port}"]["flags"]
        assert "UNHEALTHY" in by_target[
            f"127.0.0.1:{validator._http.bound_port}"
        ]["flags"]
        assert by_target[f"127.0.0.1:{survivor._http.bound_port}"][
            "healthy"
        ] is True
        text = render_table(rows)
        assert "DEAD" in text and "UNHEALTHY" in text
        # the bundle carries the black box itself, not just verdicts
        user_scrape = bundle["nodes"][0]
        ev_kinds = {
            e["kind"]
            for e in user_scrape["routes"]["/events"]["body"]["events"]
        }
        assert "stage_peer_lost" in ev_kinds and "watchdog_trip" in ev_kinds
    finally:
        live = [user, validator] + [
            w for w in workers if w is not victim
        ]
        for n in live:
            await n.stop()


async def _healthz(node) -> tuple[int, dict]:
    reader, writer = await asyncio.open_connection(
        "127.0.0.1", node._http.bound_port
    )
    writer.write(b"GET /healthz HTTP/1.1\r\n\r\n")
    await writer.drain()
    raw = await reader.read(1 << 20)
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), json.loads(body) if body else {}


# ------------------------------------------------ device-time telemetry


def test_node_row_mfu_bubble_and_host_bound_flag():
    """PR-13 columns: MFU% from the best per-program MFU (capability
    record or serving device_time), BUBBLE% from host_gap_frac, and a
    HOST-BOUND flag above 30% — the chip is waiting on the host, so
    faster silicon will not help that node."""
    def scrape(node_body):
        return {
            "target": "w:1",
            "routes": {
                "/healthz": {"status": 200, "body": {"ok": True}},
                "/node": {"status": 200, "body": {
                    "role": "worker", "node_id": "w" * 64, "peers": {},
                    **node_body,
                }},
            },
        }

    row = node_row(scrape({
        "capability": {
            "chip": "TPU v5e", "peak_tflops": 394.0, "hbm_gbps": 819.0,
            "host_gap_frac": 0.45,
            "programs": {"stage0_fwd": {"mfu": 0.38, "mean_s": 0.01}},
        },
    }), 10.0, 2.0)
    assert row["mfu_pct"] == 38.0
    assert row["bubble_pct"] == 45.0
    assert any(f.startswith("HOST-BOUND") for f in row["flags"])

    # serving device_time path; below the threshold no flag renders
    row2 = node_row(scrape({
        "serving": {"device_time": {
            "host_gap_frac": 0.12,
            "programs": {
                "decode": {"mfu": 0.06, "mbu": 0.71},
                "prefill": {"mfu": 0.41},
            },
        }},
    }), 10.0, 2.0)
    assert row2["mfu_pct"] == 41.0
    assert row2["bubble_pct"] == 12.0
    assert not any(f.startswith("HOST-BOUND") for f in row2["flags"])
    text = render_table([row, row2])
    assert "MFU%" in text and "BUBBLE%" in text and "HOST-BOUND" in text

    # no telemetry at all: columns render as dashes, nothing crashes
    bare = node_row(scrape({}), 10.0, 2.0)
    assert bare["mfu_pct"] is None and bare["bubble_pct"] is None


def _disagg_scrape(serving, capability=None):
    node_body = {
        "role": "worker", "node_id": "w" * 64, "peers": {},
        "serving": serving,
    }
    if capability is not None:
        node_body["capability"] = capability
    return {
        "target": "w:1",
        "routes": {
            "/healthz": {"status": 200, "body": {"ok": True}},
            "/node": {"status": 200, "body": node_body},
        },
    }


def test_node_row_role_column_names_serving_leg():
    """The cluster table's ROLE column appends the advertised serving
    leg from the capability record: the fleet reads as a serving
    topology (worker/prefill, worker/decode), not a process list."""
    row = node_row(_disagg_scrape(
        {}, capability={"serving_mode": "prefill"}
    ))
    assert row["role"] == "worker/prefill"
    plain = node_row(_disagg_scrape({}))
    assert plain["role"] == "worker"
    table = render_table([row])
    assert "worker/prefill" in table


def test_node_row_flags_xfer_stalled():
    """XFER-STALLED fires exactly when the wire-transfer EWMA exceeds
    the prefill-compute EWMA — the prefill worker is bound by the DCN
    hop, not its chip."""
    stalled = node_row(_disagg_scrape({
        "disagg": {"prefill_s_ewma": 0.010, "wire_s_ewma": 0.050,
                   "exports": 3},
    }, capability={"serving_mode": "prefill"}))
    assert any(f.startswith("XFER-STALLED") for f in stalled["flags"])
    healthy = node_row(_disagg_scrape({
        "disagg": {"prefill_s_ewma": 0.050, "wire_s_ewma": 0.010,
                   "exports": 3},
    }, capability={"serving_mode": "prefill"}))
    assert not any(f.startswith("XFER-STALLED") for f in healthy["flags"])
    # a decode-only worker (no transfer EWMAs at all) never flags
    silent = node_row(_disagg_scrape({
        "disagg": {"imports": 5},
    }, capability={"serving_mode": "decode"}))
    assert not any(f.startswith("XFER-STALLED") for f in silent["flags"])


# ------------------------------------------------------ tldiag proto-diff
def _proto_manifest(frames, versions=None):
    return {"schema": 1, "frames": frames, "versions": versions or {}}


def test_proto_diff_break_taxonomy():
    from tensorlink_tpu.diag import proto_manifest_diff, render_proto_diff
    old = _proto_manifest({
        "PING": {"fields": {
            "t": {"kind": "float", "required": True},
            "tag": {"kind": "str", "required": False},
        }},
        "GONE": {"fields": {}},
    }, {"KV_WIRE_SCHEMA": 1})
    new = _proto_manifest({
        "PING": {"fields": {
            "t": {"kind": "str", "required": True},       # kind change
            "tag": {"kind": "str", "required": True},     # now required
            "mode": {"kind": "str", "required": True},    # new required
            "opt": {"kind": "int", "required": False},    # additive-opt
        }},
        "FRESH": {"fields": {}},                          # new frame
    }, {"KV_WIRE_SCHEMA": 2})                             # version bump
    d = proto_manifest_diff(old, new)
    assert not d["compatible"]
    joined = " ".join(d["breaks"])
    assert "GONE: frame removed" in joined
    assert "PING.t: kind changed float -> str" in joined
    assert "PING.tag: optional field turned required" in joined
    assert "PING.mode: new required field" in joined
    assert "version KV_WIRE_SCHEMA: 1 -> 2" in joined
    assert d["pins"] == ["FRESH: frame added"]
    assert d["ok"] == ["PING.opt: optional field added"]
    text = render_proto_diff(d)
    assert "rolling upgrade: UNSAFE" in text
    assert text.count("BREAK") == len(d["breaks"])


def test_proto_diff_additive_optional_is_safe():
    from tensorlink_tpu.diag import proto_manifest_diff, render_proto_diff
    old = _proto_manifest(
        {"PING": {"fields": {"t": {"kind": "float", "required": True}}}}
    )
    new = _proto_manifest({"PING": {"fields": {
        "t": {"kind": "float", "required": True},
        "extra": {"kind": "dict", "required": False},
    }}})
    d = proto_manifest_diff(old, new)
    assert d["compatible"] and d["breaks"] == []
    assert "rolling upgrade: safe" in render_proto_diff(d)
    # kind widening to "any" (statically unknown) is not a verdict
    wide = _proto_manifest(
        {"PING": {"fields": {"t": {"kind": "any", "required": True}}}}
    )
    assert proto_manifest_diff(old, wide)["compatible"]


def test_cli_proto_diff(tmp_path, capsys):
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(_proto_manifest(
        {"PING": {"fields": {"t": {"kind": "float", "required": True}}}}
    )))
    b.write_text(json.dumps(_proto_manifest({"PING": {"fields": {}}})))
    assert main(["proto-diff", str(a), str(b)]) == 1  # break -> exit 1
    out = capsys.readouterr().out
    assert "BREAK PING.t: field removed" in out
    assert main(["proto-diff", str(a), str(a)]) == 0
    capsys.readouterr()
    assert main(["proto-diff", str(a), str(b), "--json"]) == 1
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["compatible"] is False
    assert parsed["frames"]["PING"]["t"] == "removed"


# ------------------------------------------- one harness, no dead entries
def _subcommands(main_fn, capsys) -> set[str]:
    """The subcommands a CLI's ``--help`` lists."""
    import re

    with pytest.raises(SystemExit):
        main_fn(["--help"])
    listed = re.search(r"\{([\w,\-]+)\}", capsys.readouterr().out)
    return set(listed.group(1).split(","))


def test_tldiag_help_lists_no_bench_diff(capsys):
    cmds = _subcommands(main, capsys)
    assert {"scrape", "table", "manifest-diff"} <= cmds
    assert not any("bench" in c for c in cmds)


def test_package_cli_subcommands_resolve_inside_the_package(capsys):
    """``python -m tensorlink_tpu --help`` lists exactly the commands of
    ``COMMANDS``, and each is run by a function of the package."""
    from tensorlink_tpu import __main__ as cli

    assert _subcommands(cli.main, capsys) == set(cli.COMMANDS)
    assert "bench" not in cli.COMMANDS
    for name, fn in cli.COMMANDS.items():
        assert fn.__module__ == "tensorlink_tpu.__main__", name


def test_package_reads_no_file_of_the_driver_or_outside_itself():
    """No module of the package opens the judges' and driver's records
    or runs a script that lies outside the package. String literals
    only: a comment or docstring may still cite a round."""
    import ast
    from pathlib import Path

    import tensorlink_tpu

    banned = ("BENCH_r", "MULTICHIP_r", "BASELINE.json", "VERDICT.md",
              "ADVICE.md", "bench.py")
    found = []
    for path in sorted(Path(tensorlink_tpu.__file__).parent.rglob("*.py")):
        tree = ast.parse(path.read_text())
        docstrings = {
            id(node.body[0].value)
            for node in ast.walk(tree)
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                 ast.AsyncFunctionDef))
            and node.body and isinstance(node.body[0], ast.Expr)
            and isinstance(node.body[0].value, ast.Constant)
        }
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and id(node) not in docstrings
                    and any(b in node.value for b in banned)):
                found.append(f"{path}:{node.lineno}: {node.value[:60]!r}")
            if isinstance(node, ast.Attribute) and node.attr == "run_path":
                found.append(f"{path}:{node.lineno}: runpy.run_path")
    assert not found, "\n".join(found)
