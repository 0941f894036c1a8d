"""Bench-harness logic tests: heal attribution, the phase-A remat walk,
fleet-metric aggregation, the DiLoCo quantized-wire A/B gate, and the
phase-A TPU-capture guards.

Heal attribution history: the round-3 artifact showed ``promote_s =
-5.44`` — the promoted standby and the fresh spare re-warmed behind it
interleave in one replica log, and the old phase walk attributed the
spare's boot to the heal.  The fix keys every event by writer pid and
attributes a kill only to the incarnation that logged the rejoin step.
The reference measures heal timings in its manager integration harness
(``torchft/manager_integ_test.py:340-430``).
"""

import bench


def _phases(pid, t0, *names_and_offsets):
    return [
        {"phase": name, "ts": t0 + dt, "pid": pid}
        for name, dt in names_and_offsets
    ]


class TestHealBreakdown:
    def test_cold_respawn_all_phases_nonnegative_and_sum(self):
        kill, rejoin = 100.0, 108.0
        recs = _phases(
            42,
            kill,
            ("proc_start", 1.0),
            ("jax_ready", 3.0),
            ("model_ready", 5.0),
            ("manager_ready", 6.0),
        )
        recs.append({"step": 7, "ts": rejoin, "pid": 42})
        bd = bench._heal_breakdown(recs, kill, rejoin, 42)
        assert bd["path"] == "cold"
        assert bd["sane"] is True
        assert bd["respawn_s"] == 1.0
        assert bd["jax_init_s"] == 2.0
        assert bd["model_build_s"] == 2.0
        assert bd["manager_s"] == 1.0
        assert bd["join_to_first_commit_s"] == 2.0
        total = sum(v for v in bd.values() if isinstance(v, float))
        assert abs(total - (rejoin - kill)) < 0.01

    def test_promoted_standby_ignores_interleaved_spare_boot(self):
        """The round-3 bug scenario: a spare re-warmed behind the promoted
        standby logs its boot phases inside the kill->rejoin window."""
        kill, rejoin = 100.0, 102.0
        promoted = _phases(
            10,
            kill,
            ("standby_promoted", 0.3),
            ("manager_ready", 0.5),
        )
        promoted.append(
            {
                "phase": "first_commit",
                "ts": kill + 1.9,
                "pid": 10,
                "timings": {"quorum_rpc_s": 1.0, "heal_recv_s": 0.3},
            }
        )
        promoted.append({"step": 5, "ts": rejoin, "pid": 10})
        # the fresh spare boots concurrently — a DIFFERENT incarnation
        spare = _phases(
            11,
            kill,
            ("proc_start", 0.4),
            ("jax_ready", 1.2),
            ("model_ready", 1.8),
        )
        bd = bench._heal_breakdown(promoted + spare, kill, rejoin, 10)
        assert bd["path"] == "standby"
        assert bd["sane"] is True
        assert "respawn_s" not in bd  # the spare's boot is off the heal path
        assert bd["promote_s"] == 0.3
        assert bd["manager_s"] == 0.2
        assert bd["join_to_first_commit_s"] == 1.5
        assert bd["quorum_quorum_rpc_s"] == 1.0
        assert all(
            v >= 0 for v in bd.values() if isinstance(v, (int, float))
        )

    def test_join_window_sub_attribution_telescopes(self):
        """Round-4 verdict item 3: ~8.5 s of join_to_first_commit had no
        bucket.  The worker now logs first_started / first_grads_ready /
        first_quorum_ready inside the join window; the walk must attribute
        them and leave only a small residual, with the buckets telescoping
        to exactly kill→rejoin."""
        kill, rejoin = 100.0, 115.0
        recs = _phases(
            7,
            kill,
            ("proc_start", 1.0),
            ("jax_ready", 3.0),
            ("model_ready", 5.0),
            ("manager_ready", 6.0),
            ("first_started", 6.2),
            ("first_grads_ready", 10.0),
            ("first_quorum_ready", 14.0),
        )
        recs.append({"step": 9, "ts": rejoin, "pid": 7})
        bd = bench._heal_breakdown(recs, kill, rejoin, 7)
        assert bd["sane"] is True
        assert bd["first_loop_s"] == 0.2
        assert bd["first_grads_s"] == 3.8
        assert bd["quorum_wait_s"] == 4.0
        assert bd["join_to_first_commit_s"] == 1.0
        total = sum(v for v in bd.values() if isinstance(v, float))
        assert abs(total - (rejoin - kill)) < 0.01
        # the formerly-opaque bucket is now a small residual, not the
        # majority of the heal
        attributed = total - bd["join_to_first_commit_s"]
        assert attributed / total > 0.9

    def test_legacy_records_without_pid_still_attribute(self):
        kill, rejoin = 10.0, 14.0
        recs = [
            {"phase": "proc_start", "ts": 11.0},
            {"phase": "manager_ready", "ts": 12.0},
            {"step": 3, "ts": rejoin},
        ]
        bd = bench._heal_breakdown(recs, kill, rejoin, None)
        assert bd["respawn_s"] == 1.0
        assert bd["sane"] is True


class TestPhaseARematWalk:
    """The OOM-fallback walk over remat modes (attn -> ffn -> layer)."""

    def test_falls_back_on_oom_and_stops_on_success(self, monkeypatch):
        calls = []

        def fake_mode(sizes, mode):
            calls.append(mode)
            if mode == "attn":
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return {"remat": mode}

        monkeypatch.setattr(bench, "_run_single_mode", fake_mode)
        out = bench.run_single({"remat": 1})
        assert calls == ["attn", "ffn"]
        assert out == {"remat": "ffn"}

    def test_non_oom_error_raises_immediately(self, monkeypatch):
        def fake_mode(sizes, mode):
            raise RuntimeError("Mosaic lowering failed: bad block shape")

        monkeypatch.setattr(bench, "_run_single_mode", fake_mode)
        import pytest

        with pytest.raises(RuntimeError, match="Mosaic"):
            bench.run_single({"remat": 1})

    def test_oom_on_last_mode_raises(self, monkeypatch):
        def fake_mode(sizes, mode):
            raise RuntimeError("RESOURCE_EXHAUSTED")

        monkeypatch.setattr(bench, "_run_single_mode", fake_mode)
        import pytest

        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            bench.run_single({"remat": 1})

    def test_env_override_pins_single_mode(self, monkeypatch):
        monkeypatch.setenv("TPUFT_BENCH_REMAT_MODE", "layer")
        assert bench._phase_a_modes({"remat": 1}) == ["layer"]
        monkeypatch.delenv("TPUFT_BENCH_REMAT_MODE")
        assert bench._phase_a_modes({"remat": 0}) == ["none"]
        assert bench._phase_a_modes({"remat": 1}) == ["attn", "ffn", "layer"]


class TestFleetMetricsAggregation:
    def test_breakdown_mean_only_over_kills_with_phase(self):
        """A cold heal and a standby heal in one phase must not drag each
        other's phase means toward zero."""
        t = 1000.0
        kills = [
            {"ts": t + 10.0, "survivor_step": 5, "victim": 1},
            {"ts": t + 30.0, "survivor_step": 15, "victim": 1},
        ]
        anchor = [
            {"step": i, "ts": t + i * 2.0, "pid": 1} for i in range(1, 25)
        ]
        victim = []
        # first heal: cold respawn (pid 20), rejoin at t+16
        victim += _phases(
            20, t + 10.0, ("proc_start", 2.0), ("manager_ready", 4.0)
        )
        victim += [{"step": 6, "ts": t + 16.0, "pid": 20}]
        # second heal: standby promotion (pid 30), rejoin at t+32
        victim += _phases(
            30, t + 30.0, ("standby_promoted", 0.5), ("manager_ready", 0.8)
        )
        victim += [{"step": 16, "ts": t + 32.0, "pid": 30}]
        res = bench._fleet_metrics("x", 20, [anchor, victim], kills)
        bd = res["heal_breakdown"]
        assert bd["all_sane"] is True
        assert bd["paths"] == {"cold": 1, "standby": 1}
        # respawn_s appears in ONE breakdown; mean must be over that one
        assert bd["respawn_s"] == 2.0
        assert bd["promote_s"] == 0.5
        assert res["heal_in_s"] == [6.0, 2.0]
        assert len(res["heal_breakdowns"]) == 2
        assert res["heal_in_s_by_path"] == {"cold": 6.0, "standby": 2.0}


class TestHeadlineHealKeys:
    """Round-6: the aggregated ``heal_breakdown`` phases surface as
    top-level headline keys (respawn / join / transfer / first-commit /
    promote) so the spare-promotion gate is comparable round-over-round
    without opening bench_out.json."""

    def test_lifts_phases_to_top_level(self):
        faults = {
            "heal_breakdown": {
                "respawn_s": 1.5,
                "quorum_wait_s": 2.0,
                "quorum_heal_recv_s": 3.0,
                "join_to_first_commit_s": 0.5,
                "promote_s": 0.3,
                "all_sane": True,
            }
        }
        keys = bench._headline_heal_keys(faults)
        assert keys == {
            "heal_respawn_s": 1.5,
            "heal_join_s": 2.0,
            "heal_transfer_s": 3.0,
            "heal_first_commit_s": 0.5,
            "heal_promote_s": 0.3,
        }

    def test_missing_phases_are_none_not_absent(self):
        """A phase no kill exercised this round must still be a key (None)
        so round-over-round diffs never mistake 'absent' for 'zero'."""
        keys = bench._headline_heal_keys({"heal_breakdown": {"respawn_s": 2.0}})
        assert keys["heal_respawn_s"] == 2.0
        assert keys["heal_promote_s"] is None
        assert keys["heal_transfer_s"] is None
        # no breakdown at all (fleet phase skipped): every key present, None
        assert all(v is None for v in bench._headline_heal_keys({}).values())


class TestDilocoQuantGate:
    """The measured A/B gate for the DiLoCo pseudogradient wire (round-5
    verdict item 4): both wires recorded, churn uses the measured winner,
    budget starvation degrades to f32 + reason instead of starving churn."""

    def _run(self, monkeypatch, overheads, deadline_in=None, env=None):
        import time as _time

        calls = []

        def fake_run_fleet(label, **kw):
            calls.append((label, kw.get("extra_env", {})))
            r = {"label": label, "kills": kw.get("max_kills") or 0,
                 "t_step_s": 1.0, "completed": True,
                 "ratio_per_100step_kill": 0.99}
            for wire, so in overheads.items():
                if label.endswith(wire) and so is not None:
                    r["sync_overhead_s"] = so
            return r

        monkeypatch.setattr(bench, "run_fleet", fake_run_fleet)
        if env is not None:
            monkeypatch.setenv("TPUFT_BENCH_DILOCO_QUANT", env)
        else:
            monkeypatch.delenv("TPUFT_BENCH_DILOCO_QUANT", raising=False)
        sizes = {
            "diloco_steps": 48, "diloco_sync_every": 8,
            "diloco_fragments": 2, "diloco_sync_delay": 2,
            "diloco_kills": 3,
        }
        deadline = None if deadline_in is None else _time.time() + deadline_in
        out = bench._run_diloco_phase(sizes, "cpu", 3, deadline_ts=deadline)
        return out, calls

    def test_auto_records_both_and_picks_cheaper(self, monkeypatch):
        out, calls = self._run(monkeypatch, {"f32": 0.4, "quant": 0.2})
        assert out["quantized_sync"] is True
        assert out["sync_overhead_s_f32"] == 0.4
        assert out["sync_overhead_s_quant"] == 0.2
        assert out["quant_vs_f32_sync_overhead"] == 0.5
        assert "faultfree_alt" in out
        churn_env = [e for (l, e) in calls if l == "diloco_churn"][0]
        assert churn_env["TPUFT_BENCH_DILOCO_QUANT_WIRE"] == "1"

    def test_auto_keeps_f32_when_quant_measures_slower(self, monkeypatch):
        out, calls = self._run(monkeypatch, {"f32": 0.2, "quant": 0.4})
        assert out["quantized_sync"] is False
        assert out["quant_vs_f32_sync_overhead"] == 2.0
        churn_env = [e for (l, e) in calls if l == "diloco_churn"][0]
        assert churn_env["TPUFT_BENCH_DILOCO_QUANT_WIRE"] == "0"

    def test_auto_falls_back_when_overheads_missing(self, monkeypatch):
        out, calls = self._run(monkeypatch, {"f32": None, "quant": None})
        assert out["quantized_sync"] is False
        assert "sync_overhead_s missing" in out["quant_gate_reason"]
        # the alternate run is still in the artifact, never discarded
        assert "faultfree_alt" in out

    def test_budget_starved_skips_ab_not_churn(self, monkeypatch):
        out, calls = self._run(
            monkeypatch, {"f32": 0.4, "quant": 0.2}, deadline_in=200.0
        )
        labels = [l for (l, _e) in calls]
        assert "diloco_faultfree_quant" not in labels  # A/B starved...
        assert "diloco_churn" in labels  # ...churn never is
        assert out["quantized_sync"] is False
        assert "reserved for the churn run" in out["quant_gate_reason"]

    def test_forced_wire_skips_ab(self, monkeypatch):
        out, calls = self._run(monkeypatch, {"quant": 0.2}, env="1")
        labels = [l for (l, _e) in calls]
        # forcing the wire skips the f32/quant A/B, but the replicated
        # outer-sync leg (sharded-vs-replicated trajectory row) still runs
        assert labels == [
            "diloco_faultfree_quant",
            "diloco_faultfree_replicated",
            "diloco_faultfree_streaming",
            "diloco_churn",
        ]
        assert out["quantized_sync"] is True
        assert out["quant_gate"] == "forced"
        repl_env = [e for (l, e) in calls if l == "diloco_faultfree_replicated"][0]
        assert repl_env["TORCHFT_OUTER_SHARD"] == "0"


class TestDilocoStreamingLeg:
    """The ISSUE-15 streamed outer-sync bench leg: runs on the chosen
    wire with the fragment scheduler forced on, streams into the partial
    artifact, and yields the stream_overlap_ratio / sync_overhead_frac
    summary rows; TPUFT_BENCH_SKIP_STREAM opts out and a no-staleness-room
    cadence skips it without failing the phase."""

    def _run(self, monkeypatch, overheads, sizes_over=None, env=None):
        calls = []

        def fake_run_fleet(label, **kw):
            calls.append((label, kw.get("extra_env", {})))
            r = {"label": label, "kills": kw.get("max_kills") or 0,
                 "t_step_s": 1.0, "completed": True,
                 "ratio_per_100step_kill": 0.99}
            for wire, so in overheads.items():
                if label.endswith(wire) and so is not None:
                    r["sync_overhead_s"] = so
            if label.endswith("streaming"):
                r["inner_step_s"] = 0.5
            return r

        monkeypatch.setattr(bench, "run_fleet", fake_run_fleet)
        monkeypatch.delenv("TPUFT_BENCH_DILOCO_QUANT", raising=False)
        monkeypatch.delenv("TPUFT_BENCH_SKIP_STREAM", raising=False)
        if env:
            for k, v in env.items():
                monkeypatch.setenv(k, v)
        sizes = {
            "diloco_steps": 48, "diloco_sync_every": 8,
            "diloco_fragments": 2, "diloco_sync_delay": 2,
            "diloco_kills": 3,
        }
        sizes.update(sizes_over or {})
        out = bench._run_diloco_phase(sizes, "cpu", 3, deadline_ts=None)
        return out, calls

    def test_streaming_leg_runs_with_stream_env(self, monkeypatch):
        out, calls = self._run(
            monkeypatch, {"f32": 0.4, "quant": 0.2, "streaming": 0.01}
        )
        env = [e for (l, e) in calls if l == "diloco_faultfree_streaming"][0]
        assert env["TORCHFT_STREAM_SYNC"] == "1"
        # per_frag = 8/2 = 4, delay 2 -> staleness room 1
        assert env["TORCHFT_STREAM_MAX_STALENESS"] == "1"
        # rides the measured-cheaper wire, like churn
        assert env["TPUFT_BENCH_DILOCO_QUANT_WIRE"] == "1"
        assert out["sync_overhead_s_streaming"] == 0.01
        # overlap vs the sharded (blocking) leg: 1 - 0.01/0.2
        assert out["stream_overlap_ratio"] == 0.95
        # residual over the streaming leg's inner step time: 0.01/0.5
        assert out["sync_overhead_frac"] == 0.02

    def test_skip_knob_opts_out(self, monkeypatch):
        out, calls = self._run(
            monkeypatch,
            {"f32": 0.4, "quant": 0.2, "streaming": 0.01},
            env={"TPUFT_BENCH_SKIP_STREAM": "1"},
        )
        labels = [l for (l, _e) in calls]
        assert "diloco_faultfree_streaming" not in labels
        assert "sync_overhead_s_streaming" not in out
        assert "stream_overlap_ratio" not in out
        assert "diloco_churn" in labels  # churn untouched

    def test_no_staleness_room_skips_leg(self, monkeypatch):
        # per_frag = 4, delay 3 -> room 0: the leg cannot stream
        out, calls = self._run(
            monkeypatch,
            {"f32": 0.4, "quant": 0.2, "streaming": 0.01},
            sizes_over={"diloco_sync_delay": 3},
        )
        labels = [l for (l, _e) in calls]
        assert "diloco_faultfree_streaming" not in labels
        assert "diloco_churn" in labels

    def test_missing_blocking_overhead_still_reports_frac(self, monkeypatch):
        """A pinned-legacy or overhead-less run must not lose the
        streaming residual: the frac lands even when the ratio cannot."""
        out, _calls = self._run(
            monkeypatch, {"f32": None, "quant": None, "streaming": 0.01}
        )
        assert out["sync_overhead_s_streaming"] == 0.01
        assert "stream_overlap_ratio" not in out
        assert out["sync_overhead_frac"] == 0.02
