"""Names for the parts of the step programs (code2vec_tpu/scopes.py): every
part of the packed train step is found by its ``jax.named_scope`` in the
compiled program's text, on one device and on a data-parallel mesh; the
scopes name and change nothing (the lowered text is the one without them);
a profiler capture gets the text of the programs that ran in it; and the
packer's ``host/pack`` profiler event.

CPU, tiny sizes, up to two of the eight virtual devices."""
import contextlib
import functools
import json
import re

import jax
import numpy as np
import pytest
from jax import monitoring

from code2vec_tpu import scopes
from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.telemetry import core
from code2vec_tpu.telemetry.trace import ProgramLegend, TraceController

from tests.test_touched_rows import (lowered, make_trainer, packers,
                                     plane_batch)

TRAIN_SCOPES = ('c2v_encode', 'c2v_table_grad', 'c2v_logits', 'c2v_ce',
                'c2v_adam')
OP_NAME = re.compile(r'op_name="([^"]*)"')
COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


def lowered_step(data):
    trainer = make_trainer(data, 1)
    with_rows, _ = packers(trainer)
    return lowered(trainer, with_rows.pack_batch(
        plane_batch(np.random.default_rng(3))))


@functools.lru_cache(maxsize=None)
def op_names(data):
    """Every ``op_name`` of the packed train step compiled for ``data``
    shards."""
    return tuple(OP_NAME.findall(lowered_step(data).compile().as_text()))


def innermost(op_name):
    """The part an instruction belongs to, as a reader takes it: the last
    ``c2v_`` name of its ``op_name``, wrappers of autodiff and all."""
    found = re.findall(r'c2v_\w+', op_name)
    return found[-1] if found else None


@pytest.mark.parametrize('data', [1, 2])
@pytest.mark.parametrize('scope', TRAIN_SCOPES)
def test_the_compiled_train_step_names_each_part(data, scope):
    assert scope in scopes.SCOPES
    assert [n for n in op_names(data) if innermost(n) == scope]


@pytest.mark.parametrize('data', [1, 2])
@pytest.mark.parametrize('scope,holder', [('c2v_encode', 'c2v_encode'),
                                          ('c2v_logits', 'c2v_ce')])
def test_autodiff_wraps_forward_and_backward(data, scope, holder):
    # the forward is named inside jvp(..), what autodiff derives from it
    # inside transpose(jvp(..)): a reader that compared whole components
    # of the path would find neither
    mine = [n for n in op_names(data) if innermost(n) == scope]
    forward = [n for n in mine if '/jvp(%s)/' % holder in n]
    backward = [n for n in mine if '/transpose(jvp(%s))/' % holder in n]
    assert forward and backward
    assert not [n for n in mine if '/%s/' % holder in n and scope == holder]


@pytest.mark.parametrize('data', [1, 2])
def test_the_custom_vjps_backward_names_the_table_gradients(data):
    # autodiff names no custom VJP's backward: it opens c2v_encode itself,
    # and the table gradients' scope inside it
    mine = [n for n in op_names(data) if innermost(n) == 'c2v_table_grad']
    assert mine and all(
        '/transpose(jvp(c2v_encode))/c2v_table_grad/' in n for n in mine)
    assert [n for n in mine if 'scatter' in n]


@pytest.mark.parametrize('data', [1, 2])
def test_the_scopes_name_and_change_nothing(data, monkeypatch):
    with_scopes = lowered_step(data)
    assert 'c2v_adam' in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(jax, 'named_scope',
                        lambda name: contextlib.nullcontext())
    without = lowered_step(data)
    assert 'c2v_' not in without.as_text(debug_info=True)
    # locations stripped (the default): byte for byte the same program
    assert with_scopes.as_text() == without.as_text()


def test_eval_and_predict_programs_share_the_names():
    trainer = make_trainer(1, 1)
    _, plain = packers(trainer)
    packed = plain.pack_batch(plane_batch(np.random.default_rng(5)))
    arrays = mesh_lib.shard_batch(packed.device_arrays(), trainer.mesh)
    params = trainer.init_state(seed=0).params
    for program in (trainer._eval_step_packed,
                    trainer._predict_steps[('topk', 'packed')]):
        names = OP_NAME.findall(
            program.lower(params, arrays).compile().as_text())
        assert {'c2v_encode', 'c2v_logits', 'c2v_topk'} <= {
            innermost(n) for n in names}


def test_a_scope_outside_the_catalog_is_refused():
    with pytest.raises(ValueError, match='scope catalog'):
        scopes.scoped('c2v_typo')


# ------------------------------------------------------------ the legend
class Compiles:
    """Counts the compile events (a persistent-cache hit fires one too)."""

    def __init__(self):
        self.value = 0
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.value += 1

    def close(self):
        monitoring.unregister_event_duration_listener(self)


@pytest.fixture
def compiles():
    counter = Compiles()
    yield counter
    counter.close()


def fit_packed(trainer, batches=6):
    with_rows, _ = packers(trainer)
    rng = np.random.default_rng(11)
    packed = [with_rows.pack_batch(plane_batch(rng)) for _ in range(batches)]
    trainer.fit(trainer.init_state(seed=0), lambda epoch: iter(packed))
    return packed


def legend_of(capture_dir):
    """{shape key: (text, about)} of the programs beside a capture."""
    out = {}
    for path in sorted((capture_dir / 'programs').glob('*.json')):
        about = json.loads(path.read_text())
        text = path.with_name(path.name[:-len('.json')] + '.hlo.txt')
        out[about['shape_key']] = (text.read_text(), about)
    return out


@pytest.mark.parametrize('data', [1, 2])
def test_a_profile_dir_capture_holds_the_programs_that_ran(
        data, tmp_path, monkeypatch, compiles):
    # PROFILE_DIR alone, telemetry off: the seam computes the shape key
    # itself. The profiler is replaced by a recorder of the compile count
    # at start and stop: no program is built or loaded between them
    marks = []
    monkeypatch.setattr(jax.profiler, 'start_trace',
                        lambda path: marks.append(('start', compiles.value)))
    monkeypatch.setattr(jax.profiler, 'stop_trace',
                        lambda: marks.append(('stop', compiles.value)))
    trainer = make_trainer(data, 1, NUM_TRAIN_EPOCHS=1,
                           PROFILE_DIR=str(tmp_path / 'trace'),
                           PROFILE_START_STEP=2, PROFILE_NUM_STEPS=2)
    packed = fit_packed(trainer)
    assert [m[0] for m in marks] == ['start', 'stop']
    assert marks[0][1] == marks[1][1] > 0
    found = legend_of(tmp_path / 'trace')
    key = 'packed:%d:%d:%d' % (packed[2].ctx.shape[1],
                               packed[2].tok_rows.size,
                               packed[2].path_rows.size)
    assert list(found) == [key]
    text, about = found[key]
    assert text.startswith('HloModule ' + about['module'])
    assert {innermost(n) for n in OP_NAME.findall(text)} >= set(TRAIN_SCOPES)
    assert about['mesh'] == {'data': data, 'model': 1}
    shapes = {path: leaf['shape'] for path, leaf in about['params'].items()}
    state = trainer.init_state(seed=0)
    assert shapes == {jax.tree_util.keystr(path): list(leaf.shape)
                      for path, leaf in
                      jax.tree_util.tree_leaves_with_path(state.params)}
    assert {leaf['dtype'] for leaf in about['opt_state'].values()} >= {
        'float32'}


def test_a_capture_cut_short_gets_its_legend_too(tmp_path, monkeypatch):
    # the run ends inside the window: fit's ``finally`` stops the capture
    stops = []
    monkeypatch.setattr(jax.profiler, 'start_trace', lambda path: None)
    monkeypatch.setattr(jax.profiler, 'stop_trace',
                        lambda: stops.append(1))
    trainer = make_trainer(1, 1, NUM_TRAIN_EPOCHS=1,
                           PROFILE_DIR=str(tmp_path / 'trace'),
                           PROFILE_START_STEP=1, PROFILE_NUM_STEPS=100)
    fit_packed(trainer, batches=3)
    assert stops == [1]
    assert len(legend_of(tmp_path / 'trace')) == 1


def test_without_a_capture_to_come_the_trainer_keeps_no_legend():
    trainer = make_trainer(1, 1, NUM_TRAIN_EPOCHS=1)
    assert trainer._legend is None
    fit_packed(trainer, batches=2)
    assert not trainer._seen_keys


def test_an_on_demand_capture_holds_the_programs_that_ran(
        tmp_path, monkeypatch, compiles):
    marks = []
    monkeypatch.setattr(jax.profiler, 'start_trace',
                        lambda path: marks.append(('start', compiles.value)))
    monkeypatch.setattr(jax.profiler, 'stop_trace',
                        lambda: marks.append(('stop', compiles.value)))
    trainer = make_trainer(1, 1, NUM_TRAIN_EPOCHS=1, TELEMETRY=True,
                           TELEMETRY_DIR=str(tmp_path / 'tele'),
                           TELEMETRY_TRACE_AT_STEP=2,
                           TELEMETRY_TRACE_NUM_STEPS=2)
    try:
        fit_packed(trainer)
    finally:
        core.disable()
    assert [m[0] for m in marks] == ['start', 'stop']
    assert marks[0][1] == marks[1][1] > 0
    found = legend_of(tmp_path / 'tele' / 'traces' / 'step2')
    assert len(found) == 1
    (text, about), = found.values()
    assert 'c2v_adam' in text and about['shape_key'].startswith('packed:')


class FakeLowered:
    def __init__(self, name):
        self.name = name

    def compile(self):
        return self

    def as_text(self):
        return 'HloModule %s, is_scheduled=true\n\nENTRY %%main {}\n' \
            % self.name


class FakeState:
    params = {'w': np.zeros((3, 2), np.float32)}
    opt_state = ({'mu': np.zeros((3, 2), np.float16)},)


def test_the_legend_writes_what_ran_since_the_last_capture(tmp_path):
    legend = ProgramLegend({'data': 1, 'model': 1})
    legend.add('packed:8:4:2', FakeLowered('jit_train_step'), FakeState)
    legend.add('packed:16:4:2', FakeLowered('jit_train_step'), FakeState)
    legend.ran('packed:16:4:2')
    legend.ran('packed:16:4:2')
    legend.ran('planes:4')      # never handed over: nothing to write
    first = legend.write(str(tmp_path / 'a'))
    assert [p.rsplit('/', 1)[1] for p in first] == [
        'jit_train_step.packed-16-4-2.hlo.txt']
    about = json.loads((tmp_path / 'a' / 'programs'
                        / 'jit_train_step.packed-16-4-2.json').read_text())
    assert about['shape_key'] == 'packed:16:4:2'
    assert about['params'] == {"['w']": {'shape': [3, 2],
                                         'dtype': 'float32'}}
    assert about['opt_state'] == {"[0]['mu']": {'shape': [3, 2],
                                                'dtype': 'float16'}}
    # two capacities: two programs under one module name, a file each,
    # and only the one that ran in this capture
    legend.ran('packed:8:4:2')
    second = legend.write(str(tmp_path / 'b'))
    assert [p.rsplit('/', 1)[1] for p in second] == [
        'jit_train_step.packed-8-4-2.hlo.txt']
    assert legend.write(str(tmp_path / 'c')) == []
    assert not (tmp_path / 'c').exists()


def test_the_controller_writes_the_legend_when_it_stops(tmp_path,
                                                       monkeypatch):
    monkeypatch.setattr(jax.profiler, 'start_trace', lambda path: None)
    monkeypatch.setattr(jax.profiler, 'stop_trace', lambda: None)
    controller = TraceController(str(tmp_path), trace_at_step=1,
                                 num_steps=1)
    controller.legend = ProgramLegend({'data': 1})
    controller.legend.add('planes:4', FakeLowered('jit_train_step'),
                          FakeState)
    controller.maybe_update(1)
    controller.legend.ran('planes:4')
    controller.maybe_update(2)
    assert (tmp_path / 'traces' / 'step1' / 'programs'
            / 'jit_train_step.planes-4.hlo.txt').is_file()
    # and at shutdown, for a capture left running
    controller.trace_at_step = 5
    controller.maybe_update(5)
    controller.legend.ran('planes:4')
    controller.shutdown()
    assert (tmp_path / 'traces' / 'step5' / 'programs'
            / 'jit_train_step.planes-4.json').is_file()


# ------------------------------------------------------------- host/pack
class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: what was opened."""
    opened = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        Annotations.opened.append((self.name, self.stats))

    def __exit__(self, *exc):
        return None


@pytest.mark.parametrize('telemetry', [True, False])
def test_one_opening_feeds_the_pack_timer_and_a_profiler_event(
        telemetry, monkeypatch):
    monkeypatch.setattr(jax.profiler, 'TraceAnnotation', Annotations)
    Annotations.opened = []
    packer = packed_lib.StickyPacker(0, 0, data_shards=2, minimum=8,
                                     table_rows=(40, 16))
    batch = plane_batch(np.random.default_rng(7))
    registry = core.registry()
    before = registry.timer('step/pack_ms').count
    if telemetry:
        core.enable()
    try:
        first = packer.pack_batch(batch)
        packer.pack_batch(batch)
    finally:
        core.disable()
    taken = registry.timer('step/pack_ms').count - before
    if not telemetry:
        assert Annotations.opened == [] and taken == 0
        return
    assert taken == 2
    # the stats are the sticky capacities a batch met: the first meets
    # the minimum, the second what the first grew them to
    assert Annotations.opened == [
        ('host/pack', {'capacity': 8, 'tok_rows': 8, 'path_rows': 8}),
        ('host/pack', {'capacity': first.ctx.shape[1],
                       'tok_rows': first.tok_rows.size,
                       'path_rows': first.path_rows.size})]
