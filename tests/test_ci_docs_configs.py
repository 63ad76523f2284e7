"""Static validation of the CI and docs configs.

Neither can EXECUTE in this sandbox (no CI runner, sphinx not installed —
SURVEY §2.5 packaging row), so this pins what is checkable: the YAML
parses with the structure GitHub Actions requires, every repo file a run
command mentions exists, and ``docs/conf.py`` compiles and exposes the
settings sphinx reads.  A syntax error in either would otherwise survive
until the first run in a real environment.
"""

import os
import re
import sys

import pytest

yaml = pytest.importorskip('yaml')  # declared in the test extra

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_ci():
    with open(os.path.join(REPO, '.github', 'workflows', 'ci.yml')) as f:
        return yaml.safe_load(f)


def test_ci_yaml_parses_with_actions_structure():
    ci = _load_ci()
    # PyYAML parses the `on:` key as boolean True (YAML 1.1) — accept both.
    assert 'on' in ci or True in ci
    assert 'jobs' in ci and ci['jobs']
    for name, job in ci['jobs'].items():
        assert 'runs-on' in job, name
        assert 'steps' in job and job['steps'], name
        for step in job['steps']:
            assert 'uses' in step or 'run' in step, (name, step)


def test_ci_matrix_is_three_pythons():
    job = _load_ci()['jobs']['tests']  # by name: unpacking by-strategy
    pys = job['strategy']['matrix']['python-version']  # breaks opaquely
    assert len(pys) >= 3, 'VERDICT recorded a 3-python matrix: %r' % pys


def test_ci_run_commands_reference_real_paths():
    run_text = '\n'.join(s['run'] for j in _load_ci()['jobs'].values()
                         for s in j['steps'] if 'run' in s)
    assert 'pytest' in run_text
    # Every explicit repo path in a run command must exist — including the
    # adapter job's individual test files (renaming one must fail HERE,
    # not on the first real CI run).  Paths are extracted ONLY from
    # whitespace-delimited argv tokens (ADVICE r05 #4): a token is a path
    # when it starts with a known top dir (after an optional `--opt=` or
    # `./` prefix) followed by at least one '/' segment.  Slash-less
    # prose words ('docs', 'tests'), the 'petastorm' inside console-
    # script names like `petastorm-tpu-doctor`, and substrings buried
    # mid-token can't match; `--ignore=tests/x` and
    # `tests/test_x.py::test_y` still are (the '::' selector is cut by
    # the segment charset).
    # Optional `name=` prefix (covers `--ignore=...` AND env-var
    # assignments like `DATA=tests/x.parquet`) and optional quote: such
    # paths must keep being existence-checked, not silently drop out.
    token_pattern = re.compile(
        r'^(?:[\w\-]+=)?[\'"]?(?:\./)?'
        r'((?:tests|petastorm_tpu|petastorm|examples|docs)(?:/[\w.\-]+)+)')
    # Sub-split on , and : so multi-path tokens (`--ignore=a.py,b.py`,
    # PYTHONPATH-style lists, `a.py::test_x`) check EVERY embedded path.
    paths = [m.group(1).rstrip('/.') for tok in run_text.split()
             for sub in re.split(r'[,:]', tok)
             for m in [token_pattern.match(sub)] if m]
    assert paths, 'no repo paths found in ci.yml run commands'
    for p in paths:
        assert os.path.exists(os.path.join(REPO, p)), \
            'ci.yml references missing path %r' % p


def test_ci_lint_job_gates_on_ptlint_and_ruff():
    """The lint job must run the repo-aware gate from the bare checkout
    (stdlib-only: `python -m petastorm_tpu.analysis`) AND the generic
    ruff subset — renaming either invocation must fail here, not on the
    first real CI run (ISSUE 4)."""
    job = _load_ci()['jobs']['lint']
    run_text = '\n'.join(s['run'] for s in job['steps'] if 'run' in s)
    assert 'python -m petastorm_tpu.analysis petastorm_tpu/' in run_text
    # ISSUE 11: the deadlock-analysis gate runs from the same bare
    # checkout, right next to the lint gate.
    assert 'python -m petastorm_tpu.analysis.lockdep --check ' \
           'petastorm_tpu/' in run_text
    assert 'ruff check' in run_text
    # ISSUE 19: the protocol models verify from the same bare checkout.
    assert 'python -m petastorm_tpu.analysis.protocol --check' in run_text
    # The gate stays JAX-free: no dependency install beyond ruff.
    assert 'pip install -e' not in run_text


def test_ci_tier1_names_its_slowest_tests():
    """The tier-1 suite runs against a hard time budget on some hosts;
    the pytest invocation must carry --durations so every run names its
    slowest tests (ISSUE 2 satellite)."""
    job = _load_ci()['jobs']['tests']
    run_text = '\n'.join(s['run'] for s in job['steps'] if 'run' in s)
    assert '--durations=25' in run_text


def test_docs_carry_ingest_plane_rows():
    """ISSUE 14 docs: the ingest plane's kwargs, kill switch, regime and
    counters stay documented."""
    perf = open(os.path.join(REPO, 'docs', 'performance.md')).read()
    for needle in ('ingest_window', 'PETASTORM_TPU_NO_INGEST_PLANE'):
        assert needle in perf, needle
    api = open(os.path.join(REPO, 'docs', 'api.md')).read()
    assert '`ingest`' in api and '`ingest_window`' in api
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    for needle in ('fetch-bound', 'ingest_degraded', 'ingest_wait',
                   'sched_ingest_window'):
        assert needle in obs, needle


def test_docs_carry_tenancy_and_autoscaler_rows():
    """ISSUE 16 docs: data_service.md must document fleet sharing
    (registration, WDRR fair share, admission, quotas, the v2 ledger
    table) and the autoscaler (control law, damping, kill switch);
    observability.md must carry the tenant-starved regime, the tenants
    / autoscale stats rollups, and the doctor's autoscaler probe."""
    ds = open(os.path.join(REPO, 'docs', 'data_service.md')).read()
    for needle in ('Sharing a fleet', 'register_tenant_job',
                   'max_tenant_jobs', 'retry_after_s',
                   'tenant_shm_quota_bytes', 'tenant_cache_quota_bytes',
                   'PETASTORM_TPU_NO_AUTOSCALE', '--autoscale',
                   'autoscale_storm'):
        assert needle in ds, needle
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    for needle in ('tenant-starved', 'starved_tenants', 'grants_delta',
                   'scale_outs', 'suppressed',
                   'PETASTORM_TPU_NO_AUTOSCALE'):
        assert needle in obs, needle


def test_chaos_cli_registered_and_ci_runs_the_smoke():
    """ISSUE 15/16: the chaos harness entry point must stay registered
    and the CI tests job must run the fast 4-scenario smoke (the
    invariant gate on every PR, scale-storm included); the catalogue
    itself must keep the >= 6-scenario acceptance floor."""
    src = open(os.path.join(REPO, 'pyproject.toml')).read()
    block = re.search(r'\[project\.scripts\](.*?)(\n\[|$)', src, re.S)
    assert 'petastorm-tpu-chaos' in block.group(1)
    job = _load_ci()['jobs']['tests']
    run_text = '\n'.join(s['run'] for s in job['steps'] if 'run' in s)
    assert 'python -m petastorm_tpu.test_util.chaos matrix --smoke' \
        in run_text
    from petastorm_tpu.test_util import chaos
    assert len(chaos.SCENARIOS) >= 6
    assert len(chaos.SMOKE_SCENARIOS) == 4
    assert 'autoscale_storm' in chaos.SMOKE_SCENARIOS


def test_docs_carry_control_plane_rows():
    """ISSUE 15 docs: data_service.md must document the ledger file
    format, drain semantics, the chaos CLI, and the backoff policy
    (the 'Operating the control plane' section + failure-matrix rows);
    observability.md must carry the new regime, counters, and
    verdicts."""
    ds = open(os.path.join(REPO, 'docs', 'data_service.md')).read()
    for needle in ('Operating the control plane', 'ledger_path',
                   'dispatcher_ledger', 'drain_timeout_s',
                   'petastorm-tpu-chaos', 'PETASTORM_TPU_CHAOS',
                   'PETASTORM_TPU_NO_BACKOFF_JITTER',
                   'ledger_restores'):
        assert needle in ds, needle
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    for needle in ('control-plane-degraded', 'ledger_restores',
                   'drain_timeouts', 'retry_giveups',
                   'dispatcher-restarts', 'drain-timeout'):
        assert needle in obs, needle


def test_docs_carry_provenance_plane_rows():
    """ISSUE 13 docs: observability.md must document the provenance
    record model, the explain CLI, the kill switch, the SLO watchdog,
    tail exemplars, the top --json contract sample, and the flight-dump
    hygiene sweep."""
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    for needle in ('petastorm-tpu-explain', 'PETASTORM_TPU_NO_PROVENANCE',
                   'batch_slo_ms',
                   'sweep_dumps', 'provenance_slo_',
                   'test_top_json_golden_schema', 'dump_provenance'):
        assert needle in obs, needle


def test_docs_span_catalogue_synced_with_code():
    """ISSUE 13 satellite: the docs span-catalogue and stall-component
    tables drifted across PRs 6-9 — pin them to the LIVE names.  Every
    STALL_COMPONENTS component and every span name it reads must appear
    in docs/observability.md, as must every span name the tree actually
    records (the literal catalogue below is the shipping set; extending
    the code means extending the docs AND this list)."""
    from petastorm_tpu.telemetry.spans import STALL_COMPONENTS
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    for component, names in STALL_COMPONENTS.items():
        assert '`%s`' % component in obs, component
        for name in names:
            assert name in obs, name
    live_spans = (
        'data_wait', 'step', 'data_wait_warmup', 'step_warmup',
        'host_batch', 'transform', 'device_put',
        'service/split_wait', 'service/decode_split',
        'service/serve_cached_split', 'service/serialize',
        'service/shm_publish', 'pool/process', 'pool/publish',
        'h2d/stage', 'h2d/dispatch', 'h2d/commit', 'cache/fill',
        'ingest/fetch', 'ingest/hedge')
    for name in live_spans:
        assert name in obs, 'span %r missing from the docs catalogue' % name
    # ...and the literal list above must itself stay live: each name is
    # recorded somewhere in the source tree.
    tree = []
    for root, _, files in os.walk(os.path.join(REPO, 'petastorm_tpu')):
        for name in files:
            if name.endswith('.py'):
                tree.append(open(os.path.join(root, name)).read())
    source = '\n'.join(tree)
    for name in live_spans:
        if name.endswith('_warmup'):
            # built as '<base>' + '_warmup' in StallMonitor.wrap
            assert "'_warmup'" in source and \
                "'%s'" % name[:-len('_warmup')] in source, name
            continue
        assert "'%s'" % name in source, \
            'span %r pinned here but no longer recorded in the tree' % name


def test_cluster_cache_config_and_cli_surfaces():
    """ISSUE 10 entry-point-free surfaces: the ServiceConfig kwarg (and
    its job_info field), the dispatcher/worker CLI flags, the per-worker
    plane-dir override and the doctor's --dispatcher flag."""
    import inspect

    from petastorm_tpu.service import ServiceConfig, Worker
    from petastorm_tpu.service import cli as service_cli

    fields = {f.name for f in __import__('dataclasses').fields(
        ServiceConfig)}
    assert 'cluster_cache' in fields
    config = ServiceConfig('file:///x', cache_plane=True,
                           cache_plane_dir='/tmp/p')
    assert config.cluster_cache is True          # defaults to cache_plane
    assert config.job_info(1)['cluster_cache'] is True
    assert ServiceConfig('file:///x').cluster_cache is False
    assert 'cache_plane_dir' in inspect.signature(
        Worker.__init__).parameters
    src = inspect.getsource(service_cli)
    assert '--no-cluster-cache' in src
    assert '--cache-plane-dir' in src
    doctor_src = open(os.path.join(
        REPO, 'petastorm_tpu', 'tools', 'doctor.py')).read()
    assert "'--dispatcher'" in doctor_src


def test_docs_conf_compiles_and_has_sphinx_settings():
    path = os.path.join(REPO, 'docs', 'conf.py')
    src = open(path).read()
    code = compile(src, path, 'exec')  # a SyntaxError fails the suite
    ns = {}
    old_path, old_cwd = list(sys.path), os.getcwd()
    try:
        # conf.py computes sys.path entries relative to CWD (sphinx execs
        # it from docs/); match that, and undo its sys.path side effects so
        # later-collected tests can't be shadowed by repo-parent modules.
        os.chdir(os.path.join(REPO, 'docs'))
        exec(code, ns)
    finally:
        sys.path[:] = old_path
        os.chdir(old_cwd)
    assert ns.get('project')
    assert isinstance(ns.get('extensions'), list) and ns['extensions']
    # every doc page conf/index reference exists
    for page in ('index.md', 'api.md', 'architecture.md', 'performance.md',
                 'migration.md', 'deployment.md', 'data_service.md',
                 'development.md', 'configuration.md'):
        assert os.path.exists(os.path.join(REPO, 'docs', page)), page


def test_console_script_entry_points_resolve():
    """Every [project.scripts] target must import and be callable — a typo
    there only surfaces at install time otherwise (pip builds the shim
    without validating the reference)."""
    import importlib

    src = open(os.path.join(REPO, 'pyproject.toml')).read()
    block = re.search(r'\[project\.scripts\](.*?)(\n\[|$)', src, re.S)
    assert block, 'no [project.scripts] section'
    lines = [l for l in block.group(1).strip().splitlines() if '=' in l]
    assert len(lines) >= 8, lines  # reference-parity CLIs + data service
    names = [l.split('=', 1)[0].strip() for l in lines]
    assert 'petastorm-tpu-data-service' in names, names
    # ISSUE 7: the diagnosis CLI must stay registered
    assert 'petastorm-tpu-diagnose' in names, names
    # ISSUE 11: the deadlock-analysis CLI
    assert 'petastorm-tpu-lockdep' in names, names
    # ISSUE 13: the per-batch provenance explainer
    assert 'petastorm-tpu-explain' in names, names
    # ISSUE 19: the protocol model checker
    assert 'petastorm-tpu-model' in names, names
    # ISSUE 20: the control-plane decision explainer
    assert 'petastorm-tpu-why' in names, names
    for line in lines:
        _, target = [s.strip().strip('"') for s in line.split('=', 1)]
        mod, fn = target.split(':')
        assert callable(getattr(importlib.import_module(mod), fn)), target


def test_docs_makefile_targets():
    mk = open(os.path.join(REPO, 'docs', 'Makefile')).read()
    assert 'html' in mk and 'sphinx' in mk.lower()


# -- petastorm-tpu-lint CLI (ISSUE 4 satellite): exit codes, baseline
# write mode, suppression parsing — pinned next to the other console
# scripts so a CLI regression fails HERE, not in a CI run.

def _lint_main(argv, capsys=None):
    from petastorm_tpu.analysis import main
    return main(argv)


def test_lint_cli_exit_0_on_clean_tree(tmp_path):
    (tmp_path / 'ok.py').write_text('x = 1\n')
    assert _lint_main([str(tmp_path)]) == 0


def test_lint_cli_exit_1_on_findings(tmp_path, capsys):
    mod = tmp_path / 'leaky.py'
    mod.write_text('import os\n\ndef f(fd, b):\n    os.write(fd, b)\n')
    assert _lint_main([str(mod), '--no-baseline']) == 1
    out = capsys.readouterr().out
    # The documented finding format: path:line rule-id message.
    assert 'leaky.py:4 short-write' in out


def test_lint_cli_exit_2_on_usage_errors(tmp_path):
    import pytest
    assert _lint_main([str(tmp_path / 'nope')]) == 2
    assert _lint_main(['--select', 'not-a-rule', str(tmp_path)]) == 2
    with pytest.raises(SystemExit) as exc:  # argparse's own usage error
        _lint_main(['--not-a-flag'])
    assert exc.value.code == 2


def test_lint_cli_write_baseline_then_green(tmp_path, capsys):
    mod = tmp_path / 'leaky.py'
    mod.write_text('import os\n\ndef f(fd, b):\n    os.write(fd, b)\n')
    baseline = str(tmp_path / 'baseline.txt')
    assert _lint_main([str(mod), '--baseline', baseline,
                       '--write-baseline']) == 0
    # Grandfathered: the same tree is now green against that baseline...
    assert _lint_main([str(mod), '--baseline', baseline]) == 0
    capsys.readouterr()
    # ...but a NEW finding still fails, and only the new one prints.
    mod.write_text('import os\n\ndef f(fd, b):\n    os.write(fd, b)\n'
                   '\ndef g(fd, b):\n    os.write(fd, b)\n')
    assert _lint_main([str(mod), '--baseline', baseline]) == 1
    out = capsys.readouterr().out
    assert out.count('short-write') == 1 and ':7 ' in out


def test_lint_cli_inline_suppression_parsing(tmp_path):
    mod = tmp_path / 'sup.py'
    mod.write_text(
        'import os\n\ndef f(fd, b):\n'
        '    os.write(fd, b)'
        '  # ptlint: disable=short-write — 8-byte stamp, single write\n')
    assert _lint_main([str(mod), '--no-baseline']) == 0
    # The suppression is rule-scoped: disabling another rule keeps the
    # finding alive.
    mod.write_text(
        'import os\n\ndef f(fd, b):\n'
        '    os.write(fd, b)  # ptlint: disable=flock-discipline\n')
    assert _lint_main([str(mod), '--no-baseline']) == 1


def test_conftest_arms_faulthandler():
    """The tier-1 suite dies at a hard external timeout on some hosts and
    has segfaulted natively before (PR 1) — conftest must arm
    faulthandler with a pre-timeout dump so those runs end with
    tracebacks instead of silence (ISSUE 4 satellite)."""
    src = open(os.path.join(REPO, 'tests', 'conftest.py')).read()
    assert 'faulthandler.enable()' in src
    assert re.search(r'dump_traceback_later\(timeout=timeout_s,'
                     r'\s*repeat=True,\s*\n\s*exit=False', src)
    assert "'PETASTORM_TPU_FAULT_TIMEOUT', 800" in src


def test_conftest_watchdog_dump_survives_pytest_capture(tmp_path):
    """End-to-end: a hung suite must print thread stacks to the REAL
    stderr before the external kill.  pytest's fd-capture swallows a
    naively-armed dump (the bug the conftest works around), so this
    spawns a pytest run with the watchdog at 2s over a 5s-sleeping test
    and asserts the dump reached the process output."""
    import shutil
    import subprocess

    # conftest discovery follows the TEST FILE's ancestors, so the real
    # conftest is copied next to the hang test — this drives the very
    # file the repo ships.
    shutil.copy(os.path.join(REPO, 'tests', 'conftest.py'),
                str(tmp_path / 'conftest.py'))
    test = tmp_path / 'test_hang.py'
    test.write_text('import time\n\ndef test_hangs():\n    time.sleep(5)\n')
    env = dict(os.environ, PETASTORM_TPU_FAULT_TIMEOUT='2',
               JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, '-m', 'pytest', str(test), '-q',
         '-p', 'no:cacheprovider', '-p', 'no:randomly'],
        cwd=str(tmp_path),
        env=env, capture_output=True, text=True, timeout=120)
    merged = out.stdout + out.stderr
    assert out.returncode == 0, merged
    assert 'Timeout (0:00:02)' in merged, \
        'watchdog dump did not reach the real stderr:\n%s' % merged[-2000:]
    assert 'test_hangs' in merged.split('Timeout (0:00:02)', 1)[1]


def test_pyproject_carries_ruff_config():
    src = open(os.path.join(REPO, 'pyproject.toml')).read()
    assert '[tool.ruff' in src
    block = re.search(r'\[tool\.ruff\.lint\](.*?)\n\[', src, re.S)
    assert block and re.search(r'select\s*=', block.group(1))
    assert '[tool.ruff.lint.per-file-ignores]' in src
    assert '"petastorm/**"' in src  # legacy alias package stays ignored


def test_ci_uploads_telemetry_dump_on_failure():
    """A red/hung tier-1 run must ship the conftest telemetry dump as an
    artifact (ISSUE 5 satellite) — the timeline IS the bug report for
    the silent-death class."""
    job = _load_ci()['jobs']['tests']
    uploads = [s for s in job['steps']
               if str(s.get('uses', '')).startswith('actions/upload-artifact')]
    assert uploads, 'tests job lost its telemetry-dump upload step'
    step = uploads[0]
    assert step.get('if') == 'failure()'
    assert 'test-artifacts' in step['with']['path']


def test_docs_carry_lockdep_rule_catalogue_and_dump_rows():
    """ISSUE 11 docs: development.md must catalogue the new rules and
    explain the lockdep plane (graph reading, --dot, when to suppress);
    observability.md must document the watchdog artifact's lockdep
    section."""
    dev = open(os.path.join(REPO, 'docs', 'development.md')).read()
    for rule_id in ('lock-order-cycle', 'cv-wait-no-predicate',
                    'wire-protocol-conformance'):
        assert '`%s`' % rule_id in dev, rule_id
    assert 'petastorm-tpu-lockdep' in dev
    assert '--dot' in dev and 'PETASTORM_TPU_LOCKDEP' in dev
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    assert 'lockdep' in obs and 'violations' in obs


def test_docs_carry_protocol_models_and_env_registry():
    """ISSUE 19 docs: development.md catalogues the conformance rules
    and the protocol-models section; configuration.md is the env
    kill-switch registry of record (and is reachable from the
    toctree); data_service.md cross-links the failure matrix to the
    verified models."""
    dev = open(os.path.join(REPO, 'docs', 'development.md')).read()
    for rule_id in ('protocol-model-conformance',
                    'env-kill-switch-registry'):
        assert '`%s`' % rule_id in dev, rule_id
    assert 'petastorm-tpu-model' in dev
    assert '--chaos-spec' in dev
    index = open(os.path.join(REPO, 'docs', 'index.md')).read()
    assert '\nconfiguration\n' in index
    cfg = open(os.path.join(REPO, 'docs', 'configuration.md')).read()
    assert 'PETASTORM_TPU_NO_SHM' in cfg
    ds = open(os.path.join(REPO, 'docs', 'data_service.md')).read()
    assert 'petastorm-tpu-model' in ds


def test_conftest_arms_flight_recorder_and_writes_its_artifact():
    """The suite process must keep the always-on flight ring and land it
    as flight_recorder.json next to the telemetry dump (ISSUE 7) — the
    file CI uploads and `petastorm-tpu-diagnose --flight` reads."""
    src = open(os.path.join(REPO, 'tests', 'conftest.py')).read()
    assert "flight.enable(label='pytest')" in src
    assert "'flight_recorder.json'" in src
