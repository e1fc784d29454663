"""``reduce/step_scopes.py``, ``work_train_parts.py`` and the eleven readers
that use them.

Two recordings. ``data/train-corpus-rehearsal/programs/`` is the legend the
trainer wrote beside the capture of one CPU rehearsal of ``train-corpus``
(seed 7; the text trimmed to each instruction's name, opcode, ``calls`` and
``op_name``, the tables of source lines taken out): real scopes as autodiff
and XLA leave them. ``profiles/java14m_step`` is the committed TPU trace of
five train steps of a program from before the scopes (``test_layers.py``):
real events with real nesting, joined here with a legend the test writes
for its instruction names."""
import json
import os
import re
import shutil

import pytest

from chipbench import manifest, work, work_train_parts
from chipbench.layer_metrics.lmhybridkernels import own_seconds
from chipbench.reduce import step_scopes
from chipbench.reduce import trace as trace_lib
from chipbench.runners import common

HERE = os.path.dirname(os.path.abspath(__file__))
LEGEND = os.path.join(HERE, 'data', 'train-corpus-rehearsal')
TPU_TRACE = os.path.join(manifest.ROOT, 'profiles', 'java14m_step',
                         'plugins', 'profile', '2026_07_29_13_58_54',
                         'vm.xplane.pb')
PEAKS = {'flops_per_s_bf16': 197e12, 'hbm_bytes_per_s': 819e9,
         'ici_bytes_per_s': 200e9}
JAVA14M = work.Shapes(1301248, 911488, 261248, 128, 128, 384)
KERNELS = ['kernels.%s_ms_per_step' % part for part in
           step_scopes.PARTS + ('unscoped',)] + [
    'kernels.%s_roofline' % part for part in step_scopes.PARTS]
GAUGES = ['input.unique_row_share', 'input.row_capacity_fill']


# ------------------------------------------------------------ the legend
@pytest.fixture(scope='module')
def recorded():
    (program,) = step_scopes.read_legend(LEGEND)
    return program


@pytest.mark.parametrize('op_name,part', [
    ('jit(train_step)/jvp(c2v_encode)/dot_general', 'encode'),
    ('jit(train_step)/transpose(jvp(c2v_encode))/mul', 'encode'),
    # scopes nest: the innermost, which is the last, names the part
    ('jit(train_step)/transpose(jvp(c2v_encode))/c2v_table_grad/scatter-add',
     'table_grad'),
    ('jit(train_step)/jvp(c2v_ce)/c2v_logits/dot_general', 'logits_ce'),
    ('jit(train_step)/transpose(jvp(c2v_ce))/reduce_sum', 'logits_ce'),
    ('jit(train_step)/c2v_adam/sqrt', 'adam'),
    ('jit(train_step)/jit(_take)/gather', None),
    ('jit(eval_step)/c2v_topk/top_k', None),      # no part of the train step
    ('', None)])
def test_the_part_is_the_last_scope_wherever_autodiff_wrapped_it(op_name,
                                                                part):
    assert step_scopes.part_of(op_name) == part


def test_the_recorded_legend_names_every_part(recorded):
    assert recorded.module == 'jit_train_step'
    assert recorded.about['shape_key'] == 'packed:1024:448:320'
    assert recorded.named > 1000
    by_part = {}
    for name, part in recorded.parts.items():
        by_part.setdefault(part, []).append(name)
    assert set(by_part) == set(step_scopes.PARTS) | {None}
    assert all(len(by_part[part]) > 10 for part in step_scopes.PARTS)


def test_a_fusion_takes_its_roots_part_and_mixed_ones_are_known(recorded):
    with open(os.path.join(LEGEND, 'programs', 'jit_train_step.'
                           'packed-1024-448-320.hlo.txt')) as f:
        text = f.read()
    fusions = re.findall(r'^\s+%([\w.\-]+) = \S+ fusion\(.*calls=%([\w.]+)',
                         text, re.MULTILINE)
    assert len(fusions) > 100
    roots = dict(re.findall(
        r'^%([\w.]+) \(\.\.\.\) -> \.\.\. \{\n(?:  .*\n)*?  ROOT .*?'
        r'op_name="([^"]*)"', text, re.MULTILINE))
    checked = 0
    for fusion, computation in fusions:
        if computation in roots:
            assert recorded.parts[fusion] == step_scopes.part_of(
                roots[computation]), fusion
            checked += 1
    assert checked > 50
    # a mixed fusion's body names two parts; most fusions name one
    assert 0 < len(recorded.mixed) < len(fusions) / 2
    assert recorded.mixed <= {fusion for fusion, _ in fusions}


MULTI_OUTPUT = """HloModule jit_train_step, is_scheduled=true

%fused_computation.42 (...) -> ... {
  %convolution.9 = f32[261248,384] convolution(...), metadata={op_name="jit(train_step)/transpose(jvp(c2v_ce))/c2v_logits/dot_general"}
  %add.7 = f32[261248,384] add(...), metadata={op_name="jit(train_step)/c2v_adam/add"}
  %convert.5 = bf16[261248,384] convert(...), metadata={op_name="jit(train_step)/c2v_adam/convert_element_type"}
  %convert.9 = bf16[261248,384] convert(...), metadata={op_name="jit(train_step)/c2v_adam/convert_element_type"}
  ROOT %tuple.12 = T tuple(%convert.5, %add.7, %convert.9)
}

%fused_computation.7 (...) -> ... {
  %reduce.67 = f32[1024] reduce(...), metadata={op_name="jit(train_step)/jvp(c2v_ce)/reduce_max"}
  ROOT %tuple.79 = T tuple(%reduce.67, %select_n.97)
}

%fused_computation.193 (...) -> ... {
  %convolution.3 = f32[1024,384] convolution(...), metadata={op_name="jit(train_step)/transpose(jvp(c2v_ce))/c2v_logits/dot_general"}
  %reduce.4 = f32[1024] reduce(...), metadata={op_name="jit(train_step)/transpose(jvp(c2v_encode))/reduce_sum"}
  %convert.6 = bf16[1024,384] convert(...), metadata={op_name="jit(train_step)/transpose(jvp(c2v_encode))/convert_element_type"}
  ROOT %tuple.8 = T tuple(%reduce.4, %convert.6, %convolution.3)
}

%fused_computation.14.clone (...) -> ... {
  %transpose.195 = f32[40960,384] transpose(...), metadata={op_name="jit(train_step)/jvp(c2v_encode)/mul"}
  ROOT %scatter.38 = f32[1024,384] scatter(...), to_apply=%region_9.25
}

%fused_computation.119 (...) -> ... {
  %fusion.84 = f32[1024,384] fusion(...), kind=kLoop, calls=%fused_computation.120
  ROOT %fusion.82 = f32[1024,384] fusion(...), kind=kCustom, calls=%fused_computation.14.clone
}

ENTRY %main.1 (...) -> ... {
  %fusion.14 = f32[1024,384] fusion(...), kind=kCustom, calls=%fused_computation.119
  %fusion.193 = T fusion(...), kind=kOutput, calls=%fused_computation.193, metadata={op_name="jit(train_step)/transpose(jvp(c2v_ce))/c2v_logits/dot_general"}
  %fusion.34 = T fusion(...), kind=kOutput, calls=%fused_computation.42, metadata={op_name="jit(train_step)/transpose(jvp(c2v_ce))/c2v_logits/dot_general"}
  %fusion.23 = T fusion(...), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/jvp(c2v_encode)/mul"}
  %fusion.5 = f32[8] fusion(...), kind=kLoop, calls=%fused_computation.99, metadata={op_name="jit(train_step)/jvp(c2v_encode)/mul"}
}
"""


def test_a_multi_output_fusion_takes_its_outputs_part_or_its_anchors():
    # the compiler names a fusion after its anchor: the target table's
    # Adam walk with the logits' backward product fused in reads
    # dot_general, and all three of its outputs are the walk's
    program = step_scopes.parse_program(MULTI_OUTPUT)
    assert program.parts['fusion.34'] == 'adam'
    # outputs of two parts (the logits' other backward product, with a
    # row sum and a cast for the encoder's backward beside its result):
    # the anchor's, not the majority's
    assert program.parts['fusion.193'] == 'logits_ce'
    assert program.mixed == {'fusion.34', 'fusion.193'}
    # an output the text does not name has no vote
    assert program.parts['fusion.23'] == 'logits_ce'
    # a body the text lacks: the fusion's own name
    assert program.parts['fusion.5'] == 'encode'
    # a root that is a fusion again, whose own root the compiler left
    # unnamed (the segment sum's scatter): the one part the bodies name
    assert program.parts['fusion.14'] == program.parts['fusion.82'] == \
        'encode'
    assert 'fusion.14' not in program.mixed


def test_a_legend_without_scopes_reads_nothing_and_says_why(tmp_path):
    # the text an executable has when a tree without scopes filled the
    # compile cache (JAX's cache key ignores metadata), or the parent's
    programs = tmp_path / 'programs'
    shutil.copytree(os.path.join(LEGEND, 'programs'), programs)
    (path,) = programs.glob('*.hlo.txt')
    path.write_text(re.sub(
        r'op_name="[^"]*"', lambda m: m.group(0).replace('c2v_', 'some_'),
        path.read_text()))
    seconds, program, why = step_scopes._of_capture(
        'unread.xplane.pb', str(tmp_path), 'jit_train_step')
    assert seconds is None and program is None
    assert 'names no c2v_ scope' in why and 'compile cache' in why
    # and no legend at all: the parent commit
    seconds, _, why = step_scopes._of_capture(
        'unread.xplane.pb', str(tmp_path / 'nowhere'), 'jit_train_step')
    assert seconds is None and 'wrote no legend' in why


# --------------------------------------------------- events and a legend
def test_a_nested_events_time_is_counted_once():
    # a loop holds its body's events; a collective counts under no part
    program = step_scopes.Program(
        module='m', parts={'while.1': 'encode', 'fusion.1': 'encode',
                           'fusion.2': 'table_grad', 'fusion.3': 'adam',
                           'copy.9': None},
        mixed=frozenset({'fusion.2'}), named=4, about={})
    ops = sorted([
        (0.0, 1.0, 'outside.1'),                    # before the run
        (10.0, 16.0, 'while.1'), (10.5, 12.5, 'fusion.1'),
        (13.0, 15.0, 'fusion.2'),
        (16.0, 19.0, 'all-reduce.4'), (19.0, 20.0, 'fusion.3'),
        (20.0, 20.5, 'copy.9'), (20.5, 21.0, 'unknown.7'),
    ], key=lambda e: (e[0], -e[1]))
    seconds = step_scopes.reduce_device(ops, [(10.0, 21.0, 'm(1)')],
                                        [program])
    assert seconds == {'encode': 2.0 + 2.0, 'table_grad': 2.0, 'adam': 1.0,
                       'logits_ce': 0.0, 'unscoped': 1.0, 'collective': 3.0,
                       'mixed': 2.0}
    assert sum(seconds[k] for k in step_scopes.TILES) == 11.0   # the run


def test_two_capacities_each_run_takes_the_text_that_knows_it():
    small = step_scopes.Program('m', {'fusion.1': 'adam', 'fusion.2': 'adam'},
                                frozenset(), 2, {})
    large = step_scopes.Program('m', {'fusion.1': 'encode',
                                      'fusion.7': 'encode',
                                      'fusion.8': 'encode'},
                                frozenset(), 3, {})
    ops = [(0.0, 1.0, 'fusion.1'), (1.0, 2.0, 'fusion.2'),
           (5.0, 6.0, 'fusion.1'), (6.0, 7.0, 'fusion.7'),
           (7.0, 8.0, 'fusion.8')]
    seconds = step_scopes.reduce_device(
        ops, [(0.0, 2.0, 'm(11)'), (5.0, 8.0, 'm(22)')], [small, large])
    assert (seconds['adam'], seconds['encode']) == (2.0, 3.0)


def fake_legend(trace_dir, names, about):
    """A legend for the recorded TPU trace: its instructions dealt to the
    parts in turn, every seventh to none."""
    deal = ('c2v_encode', 'c2v_table_grad', 'c2v_logits', 'c2v_ce',
            'c2v_adam', 'c2v_encode', None)
    lines = ['HloModule jit_train_step, is_scheduled=true', '',
             'ENTRY %main.1 (...) -> ... {']
    for i, name in enumerate(sorted(names)):
        scope = deal[i % len(deal)]
        lines.append('  %%%s = f32[8] add(...)%s' % (
            name, '' if scope is None else
            ', metadata={op_name="jit(train_step)/jvp(%s)/add"}' % scope))
    os.makedirs(os.path.join(trace_dir, 'programs'))
    stem = os.path.join(trace_dir, 'programs', 'jit_train_step.packed-1-2-3')
    with open(stem + '.hlo.txt', 'w') as f:
        f.write('\n'.join(lines + ['}', '']))
    with open(stem + '.json', 'w') as f:
        json.dump(about, f)


@pytest.fixture
def run(tmp_path):
    """What ``run.read_layers`` hands a reader, over the recorded TPU trace
    with a legend beside it."""
    trace_dir = tmp_path / 'trace'
    os.makedirs(trace_dir)
    os.symlink(TPU_TRACE, trace_dir / 'vm.xplane.pb')
    cell = manifest.load_cell('train-corpus')
    ctx = common.Context(
        cell=cell, seed=7, trace=True, rehearsal=False, data_root='',
        run_dir=str(tmp_path), config=cell.config,
        settings=cell.config['settings'], traffic=cell.traffic)
    reduced = trace_lib.reduce_trace(str(trace_dir))
    devices = step_scopes.read_devices(TPU_TRACE, 'jit_train_step')
    with open(os.path.join(LEGEND, 'programs', 'jit_train_step.'
                           'packed-1024-448-320.json')) as f:
        about = json.load(f)
    fake_legend(str(trace_dir), {name for _, _, name in devices[0]['ops']},
                about)
    return {'cell': cell, 'log': ctx.log, 'trace': reduced, 'peaks': PEAKS,
            'obs': {'examples_per_step_per_chip': 32, 'mean_contexts': 30.0},
            'devices': devices}


def test_the_parts_tile_the_programs_own_time(run):
    traced = step_scopes.of_run(run)
    seconds = traced['seconds']
    assert traced['module'] == 'jit_train_step'
    ops, runs = run['devices'][0]['ops'], run['devices'][0]['runs']
    assert len(runs) == 5
    inside = [own for _, start, own in own_seconds(ops)
              if any(a <= start < b for a, b, _ in runs)]
    tiled = sum(seconds[k] for k in step_scopes.TILES)
    assert tiled == pytest.approx(sum(inside) / 5, rel=1e-9)
    # and that is the program's device time less the gaps between its
    # operations: within 2% of what model.device_ms_per_step reads
    _, _, module_seconds = trace_lib.top_module(run['trace'])
    assert tiled == pytest.approx(module_seconds / 5, rel=0.02)
    assert seconds['collective'] == 0.0         # one chip
    assert all(seconds[part] > 0 for part in step_scopes.PARTS)
    assert seconds['unscoped'] > 0 and seconds['mixed'] == 0.0


@pytest.mark.parametrize('name', KERNELS)
def test_each_kernels_metric_has_an_entry_and_a_reader_of_its_own(name, run):
    cell = manifest.load_cell('train-corpus-dp4')
    (entry,) = [m for m in cell.per_layer if m['name'] == name]
    assert (entry['layer'], entry['moves'], entry['source']) == (
        'kernels', 'train_examples_per_sec_per_chip', 'device_trace')
    assert entry['workloads'] == ['train-corpus', 'train-corpus-dp4']
    assert entry['unit'] == ('%' if name.endswith('_roofline') else 'ms')
    readers = manifest.layer_readers([entry])
    assert list(readers) == [name]          # not the layer's kernels.py
    values = readers[name].read(run)
    assert list(values) == [name]
    seconds = step_scopes.of_run(run)['seconds']
    part = name[len('kernels.'):].rsplit('_', 1)[0].replace('_ms_per', '')
    if name.endswith('_ms_per_step'):
        assert values[name] == pytest.approx(1e3 * seconds[part])
    else:
        shapes = work_train_parts.shapes_of(
            step_scopes.read_legend(run['log'].__self__.trace_dir)[0].about)
        assert shapes == work.Shapes(640, 512, 256, 128, 128, 384)
        floor = work.least_seconds(work_train_parts.train_step_parts(
            shapes, 32, 32 * 30.0)[part], PEAKS)
        assert values[name] == pytest.approx(
            100.0 * floor['seconds'] / seconds[part])


@pytest.mark.parametrize('name', KERNELS)
def test_the_parent_commits_side_reads_nothing(name, run):
    # no legend beside the capture: no value, no exception
    shutil.rmtree(os.path.join(run['log'].__self__.trace_dir, 'programs'))
    (entry,) = [m for m in run['cell'].per_layer if m['name'] == name]
    assert manifest.layer_readers([entry])[name].read(run) == {}


@pytest.mark.parametrize('name', GAUGES)
def test_the_input_gauges_are_read_from_the_programs_registry(name):
    from code2vec_tpu.telemetry import core
    cell = manifest.load_cell('train-corpus')
    (entry,) = [m for m in cell.per_layer if m['name'] == name]
    assert (entry['layer'], entry['moves'], entry['source'],
            entry['unit']) == ('input', 'train_examples_per_sec_per_chip',
                               'program_counter', '%')
    assert entry['workloads'] == ['train-corpus', 'train-corpus-dp4']
    reader = manifest.layer_readers([entry])[name]
    registry = core.registry()
    registry.reset()
    assert reader.read({}) == {}        # a stream that never set it
    registry.gauge(name.replace('.', '/', 1)).set(0.4321)
    try:
        assert reader.read({}) == {name: pytest.approx(43.21)}
    finally:
        registry.reset()


def test_the_new_entries_are_the_manifests_last_eleven():
    spec = manifest.read_json(manifest.DEFAULT_MANIFEST)
    assert sorted(m['name'] for m in spec['per_layer'][-11:]) == sorted(
        KERNELS + GAUGES)
    assert len(spec['per_layer']) == 72 + 11


# ------------------------------------------------------------- the work
@pytest.mark.parametrize('chips', [1, 4])
@pytest.mark.parametrize('contexts', [36864, 1024 * 35.9])
def test_the_parts_sum_to_the_whole_steps_work(chips, contexts):
    whole = work.train_step(JAVA14M, 1024, contexts, chips=chips)
    parts = work_train_parts.train_step_parts(JAVA14M, 1024, contexts,
                                              chips=chips)
    assert set(parts) == set(work_train_parts.PARTS)
    for key in ('flops', 'hbm_bytes'):
        total = sum(part[key] for part in parts.values())
        if contexts == int(contexts):
            assert total == whole[key]          # whole numbers: exactly
        else:
            assert total == pytest.approx(whole[key], rel=1e-14)
    # the collective belongs to no part
    assert all(part['collective_bytes'] == 0.0 for part in parts.values())
    assert (whole['collective_bytes'] > 0) == (chips > 1)


def test_which_bound_applies_to_which_part():
    parts = work_train_parts.train_step_parts(JAVA14M, 1024, 1024 * 35.9)
    bounds = {part: work.least_seconds(w, PEAKS)['bound']
              for part, w in parts.items()}
    assert bounds == {'encode': 'compute', 'table_grad': 'hbm',
                      'logits_ce': 'compute', 'adam': 'hbm'}
    adam = work.least_seconds(parts['adam'], PEAKS)['seconds']
    assert adam == pytest.approx(383_697_280 * 20 / 819e9)     # 9.37 ms
