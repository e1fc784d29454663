"""ISSUE 5 e2e: the full retrieval loop — extract (native extractor) →
vectors-tier predict → neighbor search — plus the service-layer build /
query orchestration and the CLI flag surface."""
import json
import os

import numpy as np
import pytest

from code2vec_tpu.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXTRACTOR = os.path.join(REPO, 'extractor', 'build', 'c2v-extract')

JAVA_SOURCE = '''
class Probe {
  int width;
  int getWidth() { return this.width; }
  void setWidth(int value) { this.width = value; }
  boolean hasWidth() { return this.width > 0; }
  void resetWidth() { this.width = 0; }
}
'''


def write_corpus_from_lines(tmp_path, lines):
    """Context lines -> .c2v corpus + .dict.c2v pickles (the vocab the
    model builds from), like a preprocessed dataset would."""
    import pickle
    prefix = tmp_path / 'ds'
    (tmp_path / 'ds.train.c2v').write_text('\n'.join(lines) + '\n')
    token_count, path_count, target_count = {}, {}, {}
    for line in lines:
        parts = line.strip().split(' ')
        target_count[parts[0]] = target_count.get(parts[0], 0) + 1
        for ctx in parts[1:]:
            if not ctx:
                continue
            s, p, t = ctx.split(',')
            token_count[s] = token_count.get(s, 0) + 1
            token_count[t] = token_count.get(t, 0) + 1
            path_count[p] = path_count.get(p, 0) + 1
    with open(str(prefix) + '.dict.c2v', 'wb') as f:
        pickle.dump(token_count, f)
        pickle.dump(path_count, f)
        pickle.dump(target_count, f)
        pickle.dump(len(lines), f)
    return prefix


@pytest.mark.skipif(not os.path.isfile(EXTRACTOR),
                    reason='extractor binary not built')
def test_extract_to_neighbors_round_trip(tmp_path):
    """Acceptance: extract real Java -> corpus + index -> paste a method
    back through the engine -> its own corpus row is the top neighbor,
    labeled with its method name, in one warm round-trip."""
    from code2vec_tpu.index.service import build_index
    from code2vec_tpu.model_api import Code2VecModel
    from code2vec_tpu.serving.extractor_bridge import Extractor

    java_path = tmp_path / 'Probe.java'
    java_path.write_text(JAVA_SOURCE)
    config = Config(MAX_CONTEXTS=32)
    lines, _unhash = Extractor(config).extract_paths(str(java_path))
    assert len(lines) == 4  # the four methods above
    prefix = write_corpus_from_lines(tmp_path, lines)

    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=32, TRAIN_BATCH_SIZE=8,
        TEST_BATCH_SIZE=8, VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8,64', INDEX_NEIGHBORS_K=4)
    model = Code2VecModel(config)
    index = build_index(model, config,
                        source=str(prefix) + '.train.c2v')
    assert index.count == 4
    with model.serving_engine(tiers=('vectors',)) as engine:
        engine.attach_index(index)
        # "paste a method": re-extract and submit each method's contexts
        for i, line in enumerate(lines):
            (result,) = engine.predict_neighbors([line], k=2,
                                                 timeout=300)
            assert result.indices[0] == i
            assert result.labels[0] == line.split()[0]
            assert abs(result.scores[0] - 1.0) < 1e-4


@pytest.fixture(scope='module')
def model():
    from code2vec_tpu.model_api import Code2VecModel
    from tests.test_train_overfit import make_dataset
    import tempfile
    import pathlib
    prefix = make_dataset(pathlib.Path(tempfile.mkdtemp('idx_serving')))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=str(prefix), DL_FRAMEWORK='jax',
        COMPUTE_DTYPE='float32', MAX_CONTEXTS=6, TRAIN_BATCH_SIZE=16,
        TEST_BATCH_SIZE=16, NUM_TRAIN_EPOCHS=1, SHUFFLE_BUFFER_SIZE=64,
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        SERVING_BATCH_BUCKETS='8,64')
    return Code2VecModel(config)


def test_build_query_and_jsonl_batch_mode(model, tmp_path):
    """--build-index + --query-neighbors equivalent: corpus-built exact
    index with labels, batch JSONL emission, self-retrieval at rank 0."""
    from code2vec_tpu.index.service import (build_index, load_index,
                                            query_neighbors_file)
    config = model.config
    corpus = config.train_data_path
    index = build_index(model, config, source=corpus,
                        out_dir=str(tmp_path / 'c.vecindex'))
    assert index.count == 60 and index.labels is not None
    n, out_path = query_neighbors_file(
        model, config, index=index, corpus_path=corpus,
        output_path=str(tmp_path / 'n.jsonl'))
    assert n == 60
    records = [json.loads(line) for line in open(out_path)]
    assert len(records) == 60
    for record in records[:8]:
        top = record['neighbors'][0]
        assert top['label'] == record['name']
        assert abs(top['score'] - 1.0) < 1e-4
    # reopen from disk at the exact tier
    reloaded = load_index(str(tmp_path / 'c.vecindex'), config, model)
    values, indices = reloaded.search(
        np.asarray(index._matrix)[:3], 1)
    assert list(indices[:, 0]) == [0, 1, 2]


def test_submit_neighbors_accepts_raw_vectors(model, tmp_path):
    from code2vec_tpu.index.service import build_index
    config = model.config
    index = build_index(model, config, source=config.train_data_path,
                        out_dir=str(tmp_path / 'v.vecindex'))
    row = np.asarray(index._matrix)[5]
    with model.serving_engine(tiers=('vectors',)) as engine:
        engine.attach_index(index)
        (result,) = engine.submit_neighbors(row, k=3).result(timeout=300)
    assert result.indices[0] == 5


def test_submit_neighbors_requires_vectors_tier_and_index(model):
    with model.serving_engine(tiers=('topk',), warmup=False) as engine:
        with pytest.raises(ValueError, match='vectors'):
            engine.attach_index(object())
        with pytest.raises(RuntimeError, match='index'):
            engine.submit_neighbors(['x y,z,w'])


def test_cli_flags_map_to_config():
    config = Config().load_from_args([
        '--load', 'm/s', '--build-index', 'corpus.c2v',
        '--index-path', 'idx.vecindex', '--query-neighbors', 'q.c2v',
        '--index-kind', 'ivf', '--index-metric', 'dot',
        '--nprobe', '4', '--index-clusters', '32', '--neighbors-k', '7',
        '--vectors-dtype', 'float16', '--export_vocab_vectors', 'vocab'])
    assert config.BUILD_INDEX_FROM == 'corpus.c2v'
    assert config.INDEX_PATH == 'idx.vecindex'
    assert config.QUERY_NEIGHBORS_PATH == 'q.c2v'
    assert config.INDEX_KIND == 'ivf'
    assert config.INDEX_METRIC == 'dot'
    assert config.INDEX_NPROBE == 4
    assert config.INDEX_CLUSTERS == 32
    assert config.INDEX_NEIGHBORS_K == 7
    assert config.VECTORS_DTYPE == 'float16'
    assert config.EXPORT_VOCAB_VECTORS == 'vocab'


def test_query_neighbors_without_index_is_rejected(tmp_path):
    config = Config(MODEL_LOAD_PATH=str(tmp_path / 's'),
                    QUERY_NEIGHBORS_PATH='q.c2v')
    with pytest.raises(ValueError, match='query-neighbors'):
        config.verify()


def test_export_vocab_vectors_files_index_as_name_store(model, tmp_path):
    """ISSUE 5 satellite: --export_vocab_vectors writes both tables in
    word2vec text format, and the target table indexes into a
    nearest-method-NAME store."""
    from code2vec_tpu.index import store as store_lib
    from code2vec_tpu.index.exact import ExactIndex
    from code2vec_tpu.vocab import VocabType
    prefix = str(tmp_path / 'vocab')
    model.save_word2vec_format(prefix + '.tokens.txt', VocabType.Token)
    model.save_word2vec_format(prefix + '.targets.txt', VocabType.Target)
    store = store_lib.build_from_word2vec(prefix + '.targets.txt')
    assert store.count == model.vocabs.target_vocab.size
    index = ExactIndex(store)
    table = model.get_vocab_embedding_as_np_array(VocabType.Target)
    _v, indices = index.search(table[2], 1)
    assert indices[0, 0] == 2
    assert index.labels[2] == model.vocabs.target_vocab.index_to_word[2]
