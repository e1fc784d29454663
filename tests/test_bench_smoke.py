"""bench.py harness smoke (BENCH_SMOKE shapes, CPU): guards the benchmark
entry point against import/config rot between rounds."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench_smoke(**env_overrides):
    """One bench.py smoke run; returns the parsed final JSON line."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO, **env_overrides)
    proc = subprocess.run([sys.executable, os.path.join(REPO, 'bench.py')],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    assert lines
    return lines, json.loads(lines[-1])


def test_bench_smoke_emits_one_json_line():
    lines, record = run_bench_smoke()
    assert len(lines) == 1
    assert set(record) == {'metric', 'value', 'unit', 'vs_baseline',
                           'recipe', 'knobs', 'wire_bytes_per_batch',
                           'peak_hbm_bytes', 'hbm_bytes_in_use',
                           'platform', 'device_kind', 'device_count'}
    # every line names the device it ran on, as JAX reports it
    assert record['platform'] == 'cpu' and record['device_count'] >= 1
    # the packed wire format must be strictly smaller at realistic fill
    wire = record['wire_bytes_per_batch']
    assert 0 < wire['packed'] < wire['planes']
    # the memory axis (ISSUE 9) rides every headline record; the CPU
    # smoke backend has no memory_stats, so the gap is an EXPLICIT null
    assert record['peak_hbm_bytes'] is None
    # a smoke line must never masquerade as the java14m number
    assert record['metric'] == 'train_examples_per_sec_SMOKE_ONLY'
    assert record['vs_baseline'] == 0.0
    assert record['value'] > 0
    assert record['recipe'] == 'default'
    # the shipped defaults (the measured 2026-07-31 winners)
    assert record['knobs'] == {'dropout_prng': 'rbg',
                               'adam_mu': 'bfloat16',
                               'adam_nu': 'bfloat16',
                               'grads': 'float32'}


def test_bench_off_tpu_exits_nonzero_without_a_result_line():
    """No chip, no number: without BENCH_SMOKE=1 a CPU run of bench.py
    must fail and print NO result line (never `value 0.0` with rc 0)."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO)
    env.pop('BENCH_SMOKE', None)
    proc = subprocess.run([sys.executable, os.path.join(REPO, 'bench.py')],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert 'needs a TPU' in proc.stderr and '"platform": "cpu"' in proc.stderr


def test_bench_recipe_parity_pins_knobs():
    """BENCH_RECIPE=parity must actually PIN the reference-parity knobs
    (not just relabel the line): the vs-V100 comparison row is only
    refreshable if the measured config is threefry + fp32 mu. The knob
    echo comes from the resolved Config, so a regression that drops the
    overrides fails here even with the label intact."""
    _, record = run_bench_smoke(BENCH_RECIPE='parity')
    assert record['recipe'] == 'parity'
    assert record['value'] > 0
    assert record['knobs'] == {'dropout_prng': 'threefry2x32',
                               'adam_mu': 'float32',
                               'adam_nu': 'float32',
                               'grads': 'float32'}


def test_bench_unknown_recipe_resolves_to_default():
    """An unknown BENCH_RECIPE must fall back to 'default' instead of
    crashing the driver. Pure import-time string resolution — no
    measurement subprocess needed."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO,
               BENCH_RECIPE='no-such-recipe')
    proc = subprocess.run(
        [sys.executable, '-c',
         'import bench; print(bench.BENCH_RECIPE, bench.RECIPE_OVERRIDES)'],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == 'default {}'


# tier-1 runtime budget (ISSUE 17): the four heaviest bench smokes
# move behind the slow marker — capture_all.sh runs the real stages
# on-chip, and test_bench_smoke_emits_one_json_line keeps the
# import/config-rot canary in tier-1
@pytest.mark.slow
def test_bench_fused_ce_smoke_runs_all_arms():
    """The staged fused-CE A/B harness must survive import/config rot:
    chip time is too expensive to spend on a crash."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'bench_fused_ce.py')],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(line)
               for line in proc.stdout.splitlines() if line.strip()]
    measures = {r['measure'] for r in records if 'measure' in r}
    assert {'step_ms_ce_xla_SMOKE_ONLY', 'step_ms_ce_fused_SMOKE_ONLY',
            'step_ms_ce_fused_rbg_bf16mu_SMOKE_ONLY'} <= measures


@pytest.mark.slow
def test_bench_pallas_ragged_smoke_runs_all_arms():
    """ISSUEs 10 + 12: the ragged-fusion A/B harness must survive
    import/config rot, run all THREE arms (unfused / fused-twin /
    fused_kernel), carry the peak-HBM fields on every arm record (None
    on the stats-less CPU backend — an explicit gap), measure the
    train-BACKWARD arm (value_and_grad step time + the grad program's
    AOT temp bytes, the residual-footprint axis), and emit both verdict
    families: fusion-vs-unpack speedups AND the kernel-vs-shipped-twin
    records that actually gate RAGGED_TRAIN_KERNEL."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'bench_pallas_ragged.py')],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(line)
               for line in proc.stdout.splitlines() if line.strip()]
    measures = {r['measure']: r for r in records if 'measure' in r}
    assert {'step_ms_ragged_train_unfused_SMOKE_ONLY',
            'step_ms_ragged_train_fused_SMOKE_ONLY',
            'step_ms_ragged_train_fused_kernel_SMOKE_ONLY',
            'step_ms_ragged_train_bwd_unfused_SMOKE_ONLY',
            'step_ms_ragged_train_bwd_fused_SMOKE_ONLY',
            'step_ms_ragged_train_bwd_fused_kernel_SMOKE_ONLY',
            'step_ms_ragged_predict_unfused_SMOKE_ONLY',
            'step_ms_ragged_predict_fused_SMOKE_ONLY',
            'ragged_fusion_train_speedup_SMOKE_ONLY',
            'ragged_fusion_train_bwd_speedup_SMOKE_ONLY',
            'ragged_fusion_predict_speedup_SMOKE_ONLY',
            'ragged_train_kernel_speedup_SMOKE_ONLY',
            'ragged_train_kernel_bwd_speedup_SMOKE_ONLY'} <= \
        set(measures)
    for name, rec in measures.items():
        if name.startswith('step_ms_'):
            assert rec['value'] > 0
            # the memory axis rides every arm record; CPU smoke has no
            # memory_stats, so the gap is an EXPLICIT null
            assert 'peak_hbm_bytes' in rec and \
                rec['peak_hbm_bytes'] is None
            assert rec['fill'] == 0.25
        if '_train_bwd_' in name and name.startswith('step_ms_'):
            # XLA:CPU supports memory_analysis, so the smoke asserts a
            # REAL temp-bytes number (on-chip it feeds the temp ratio)
            assert rec['kind'] == 'train_bwd'
            assert isinstance(rec['temp_bytes'], int)
    # the temp-bytes ratio record (the residual win axis) must ride
    assert 'ragged_fusion_train_bwd_temp_ratio_SMOKE_ONLY' in measures
    verdicts = [r for r in records if 'verdict' in r]
    assert len(verdicts) == 2
    assert verdicts[0]['verdict'] in ('keep-fused', 'keep-unfused')
    assert verdicts[1]['verdict'] in ('kernel-on', 'kernel-off')


@pytest.mark.slow
def test_bench_mesh_smoke_fixed_offered_load():
    """ISSUE 13: the serving-mesh load harness must survive import/
    config rot, drive 1- and 2-replica arms at the same fixed offered
    load with the mixed predict + submit_neighbors profile, report p99 /
    shed-rate / per-replica fill / dispatch share per arm, and show
    ZERO post-warmup compiles (mixed-tier continuous batching never
    escapes the warm ladder).  The >=1.8x admitted-throughput scaling
    at 2 replicas is physics-gated on host cores: replica threads
    cannot parallelize anything on a 1-core container (the arm records
    carry host_cores so captures stay interpretable)."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'bench_mesh.py'),
         '--replica-counts', '1,2'],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(line)
               for line in proc.stdout.splitlines() if line.strip()]
    assert all(r.get('smoke') for r in records)
    by_metric = {}
    for r in records:
        by_metric.setdefault(r['metric'], []).append(r)
    assert by_metric['mesh_capacity_rows_per_sec_1r'][0]['value'] > 0
    offered = by_metric['mesh_offered_rows_per_sec'][0]['value']
    assert offered > 0
    arms = {r['replicas']: r
            for r in by_metric['mesh_admitted_rows_per_sec']}
    assert set(arms) == {1, 2}
    for n, arm in arms.items():
        assert arm['value'] > 0
        assert arm['p50_ms'] <= arm['p99_ms']
        assert 0.0 <= arm['shed_rate'] <= 1.0
        assert len(arm['per_replica_fill']) == n
        assert len(arm['dispatch_share']) == n
        # mixed-tier continuous batching compiled NOTHING post-warmup
        assert arm['postwarm_compiles'] == 0, arm
        assert set(arm['tiers']) == {'topk', 'attention', 'neighbors'}
        # the threaded load generator held the offered schedule
        assert arm['achieved_offer_rows_per_sec'] >= 0.5 * offered, arm
    # the 1-replica arm saturates at ~2.2x capacity offered load: the
    # shed defense must actually be shedding
    assert arms[1]['shed_rate'] > 0.1, arms[1]
    # 2 replicas split the one shared queue's stream about evenly
    share = arms[2]['dispatch_share']
    assert 0.2 <= share[0] <= 0.8, share
    (scaling,) = by_metric['mesh_scaling_2x']
    assert scaling['value'] > 0
    if (os.cpu_count() or 1) >= 2:
        # the acceptance floor holds wherever replica threads can
        # actually run in parallel; a 1-core container records the
        # ratio but cannot gate on it (nothing scales on one core)
        assert scaling['value'] >= 1.8, scaling


@pytest.mark.slow
def test_bench_mesh_stepped_load_smoke():
    """ISSUE 18: the stepped-offered-load elasticity arm must survive
    import/config rot — low -> high -> low against one process replica
    with the SLO/queue-driven autoscaler live: the high step pulls a
    second replica (scale-up latency reported, cold start included),
    the low step drains it back out typed ('autoscale'), transition
    p99 is reported next to steady-state p99, and the parent compiles
    NOTHING after warmup across both transitions."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'bench_mesh.py'),
         '--stepped-load'],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(line)
               for line in proc.stdout.splitlines() if line.strip()]
    by_metric = {r['metric']: r for r in records}
    up = by_metric['mesh_stepped_scale_up_s']
    assert up['reached_2_replicas'] is True
    assert up['value'] is not None and up['value'] > 0
    assert up['scale_up_total'] >= 1
    assert up['process_capacity_rows_per_sec_1r'] > 0
    down = by_metric['mesh_stepped_scale_down_s']
    assert down['drained_to_1_replica'] is True
    assert down['value'] is not None and down['scale_down_total'] >= 1
    assert ['r1', 'autoscale'] in down['retired']
    p99 = by_metric['mesh_stepped_transition_p99_ms']
    assert p99['value'] is not None
    assert p99['steady_p99_ms'] is not None
    assert p99['postwarm_compiles'] == 0
    assert p99['typed_failures'] == 0


def _run_mesh_soak(extra_args=(), timeout=600, smoke=True):
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO)
    if smoke:
        env['BENCH_SMOKE'] = '1'
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'scripts', 'mesh_soak.py'),
         *extra_args],
        capture_output=True, text=True, timeout=timeout, env=env)
    records = [json.loads(line)
               for line in proc.stdout.splitlines() if line.strip()]
    return proc, {r['metric']: r for r in records}


@pytest.mark.slow
def test_mesh_soak_smoke_self_heals_without_losing_requests():
    """ISSUE 14: the chaos soak must survive import/config rot AND its
    assertions must hold on the smoke shapes — paced load while the
    fault grammar periodically SIGKILLs worker replicas: zero lost
    admitted requests (every future resolves, results or typed), at
    least one supervised restart actually fired, zero post-warmup
    compiles in the parent, and a bounded p99."""
    proc, by_metric = _run_mesh_soak()
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    assert all(r.get('smoke') for r in by_metric.values())
    summary = by_metric['mesh_soak_requests']
    assert summary['value'] > 0 and summary['ok'] > 0
    assert summary['lost'] == 0 and summary['untyped_failures'] == 0
    assert by_metric['mesh_soak_lost_requests']['value'] == 0
    restarts = by_metric['mesh_soak_restarts']
    assert restarts['value'] >= 1, restarts  # the chaos actually bit
    assert restarts['redispatched'] >= 0
    p99 = by_metric['mesh_soak_p99_ms']
    assert p99['value'] is not None and p99['value'] <= p99['bound_ms']
    assert by_metric['mesh_soak_postwarm_compiles']['value'] == 0
    # ISSUE 16: the soak runs with the memo tier ON and mid-soak
    # rollover drills — the cache must serve under chaos, every
    # completed rollover must have bumped the generation, and zero
    # stale serves (asserted inline by the soak: rc 0 covers it)
    memo = by_metric['mesh_soak_memo']
    assert memo['value'] > 0 and memo['hit_rate'] > 0, memo
    assert memo['rollovers'] >= 1, memo
    assert memo['generation'] >= memo['rollovers'], memo
    # ISSUE 18: the elastic drill rode the same soak — a scale-up
    # completed UNDER the kill chaos and the scaled-up replica drained
    # back out typed during a partition window (rc 0 already covers
    # the zero-lost contract across both transitions)
    scale = by_metric['mesh_soak_scale_up_ms']
    assert scale['value'] is not None and scale['rid'], scale
    drain = by_metric['mesh_soak_drain_partition_ms']
    assert drain['value'] is not None, drain
    assert drain['retired_reason'] == 'drain', drain


@pytest.mark.slow
def test_mesh_soak_full_run():
    """The full-duration chaos soak (capture_all.sh stage mesh_soak):
    same contract, real durations, socket transport."""
    proc, by_metric = _run_mesh_soak(
        extra_args=['--mode', 'socket'], timeout=900, smoke=False)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    assert by_metric['mesh_soak_lost_requests']['value'] == 0
    assert by_metric['mesh_soak_restarts']['value'] >= 1
    assert by_metric['mesh_soak_postwarm_compiles']['value'] == 0


def test_bench_index_smoke_meets_acceptance():
    """ISSUE 5 acceptance on the CPU smoke shapes: >= 10x the naive
    NumPy host loop, zero post-warmup compiles on the query path, and
    IVF recall@10 >= 0.95 at the default nprobe."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    # best-of-4 reps: the >=10x floor is a warm-dispatch-vs-numpy ratio
    # (nominal ~20x); best-of-2 was observed tipping to ~9.5x under
    # full-suite machine load, so give min() more draws rather than
    # weaken the acceptance threshold
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'bench_index.py'), '--reps', '4',
         '--arms', 'base'],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = {r['metric']: r for r in
               (json.loads(line) for line in proc.stdout.splitlines()
                if line.strip())}
    assert all(r.get('smoke') for r in records.values())
    speedup = records['index_exact_speedup_vs_numpy']
    assert speedup['value'] >= 10.0, speedup
    assert speedup['postwarm_compiles'] == 0, speedup
    recall = records['index_ivf_recall_at10']
    assert recall['value'] >= 0.95, recall
    curve = records['index_ivf_curve']['points']
    assert curve and all(
        {'nprobe', 'recall', 'queries_per_sec'} <= set(p) for p in curve)


def test_bench_index_quant_arms_smoke():
    """Quantized-tier arms (capture stage ``index_quant``) on the CPU
    smoke shapes: both kinds hit the recall floor with zero post-warmup
    compiles, PQ compresses >= 4x vs f16 (the <= 1/4 acceptance), and
    the insert arm's rows are self-findable (queryable, no rebuild)."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'bench_index.py'), '--reps', '2',
         '--arms', 'quant'],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(line) for line in proc.stdout.splitlines()
               if line.strip()]
    by_kind = {}
    for rec in records:
        if 'kind' in rec:
            by_kind.setdefault(rec['metric'], {})[rec['kind']] = rec
    for kind in ('int8', 'pq'):
        recall = by_kind['index_quant_recall_at10'][kind]
        assert recall['value'] >= 0.95, recall
        qps = by_kind['index_quant_queries_per_sec'][kind]
        assert qps['postwarm_compiles'] == 0, qps
    assert (by_kind['index_quant_queries_per_sec']['pq']
            ['compression_vs_f16']) >= 4.0
    insert = by_kind['index_quant_insert_vectors_per_sec']['pq']
    assert insert['self_hit_at1'] >= 0.9, insert
    assert insert['segments'] >= 1, insert


def test_workloads_files_stay_within_tier1_budget():
    """ISSUE 20 satellite: the scenario-traffic-plane test files ride
    tier-1 with TINY in-code profiles and one full replay drill
    (~1 s).  The headroom contract is enforced here: both files, cold
    interpreter, well under the budget.  A replay that drifts into
    minutes fails THIS assert before it eats tier-1's time."""
    import time
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, '-m', 'pytest',
         os.path.join(REPO, 'tests', 'test_workloads.py'),
         os.path.join(REPO, 'tests', 'test_workloads_replay.py'),
         '-q', '-m', 'not slow', '-p', 'no:cacheprovider'],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-500:]
    # nominal ~6s cold; 120s leaves room for a loaded machine while
    # still catching a drift into minutes
    assert elapsed < 120.0, 'workloads tier-1 tests took %.1fs' % elapsed


@pytest.mark.slow
def test_bench_scenarios_smoke_mixed_replay(tmp_path):
    """ISSUE 20: the --scenarios stage (capture_all.sh ``scenarios``)
    must survive import/config rot on the CPU smoke shapes: one
    recorded-then-replayed mixed Java+C# profile reports per-scenario
    x per-language quality + hit-rate + shed + p99, per-scenario SLO
    burn, the retrieval-vs-softmax A/B verdict (beats or ties — the
    acceptance gate), ZERO post-warmup compiles across the whole
    mixed-scenario steady state, and a stable replay fingerprint."""
    env = dict(os.environ, BENCH_SMOKE='1', JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO)
    out = tmp_path / 'scenarios.json'
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmarks',
                                      'accuracy_at_scale.py'),
         '--scenarios', '--workdir', str(tmp_path / 'wd'),
         '--out', str(out)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    records = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith('{'):
            records.append(json.loads(line))
    quality = [r for r in records if r.get('measure') ==
               'scenario_quality']
    cells = {(r['scenario'], r['language']) for r in quality}
    # the as-labeled arm plus both A/B relabelings, both languages
    assert {('java_naming', 'java'), ('csharp_naming', 'csharp'),
            ('softmax_naming', 'java'), ('softmax_naming', 'csharp'),
            ('retrieval_naming', 'java'),
            ('retrieval_naming', 'csharp')} <= cells
    for r in quality:
        assert r['requests'] == r['delivered'] + r['shed'] + r['errors']
        assert 0.0 <= r['memo_hit_rate'] <= 1.0
        assert r['p50_ms'] <= r['p99_ms']
    slo = [r for r in records if r.get('measure') == 'scenario_slo']
    assert {r['scenario'] for r in slo} >= {'java_naming',
                                            'csharp_naming'}
    (ab,) = [r for r in records if r.get('measure') == 'retrieval_ab']
    assert ab['verdict'] in ('win', 'tie'), ab  # beats or ties
    assert ab['scored'] > 0
    (compiles,) = [r for r in records
                   if r.get('measure') == 'scenario_postwarm_compiles']
    assert compiles['value'] == 0, compiles
    (fp,) = [r for r in records
             if r.get('measure') == 'scenario_replay_fingerprint']
    assert fp['admitted'] > 0 and len(fp['value']) == 64
    saved = json.loads(out.read_text())
    assert saved['fingerprint'] == fp['value']
    assert saved['retrieval_ab']['verdict'] == ab['verdict']
