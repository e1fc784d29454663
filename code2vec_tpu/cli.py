"""CLI entry point (reference code2vec.py:16-38 dispatch).

    python -m code2vec_tpu.cli --data ds --test ds.val.c2v --save models/m/s
    python -m code2vec_tpu.cli --load models/m/s --test ds.test.c2v
    python -m code2vec_tpu.cli --load models/m/s --predict
    python -m code2vec_tpu.cli --load models/m/s --release
    python -m code2vec_tpu.cli --load models/m/s --save_word2v tokens.txt
    python -m code2vec_tpu.cli --load models/m/s --bulk-vectors corpus.c2v
    python -m code2vec_tpu.cli --load models/m/s --build-index corpus.c2v
    python -m code2vec_tpu.cli --load models/m/s \
        --index-path corpus.c2v.vecindex --query-neighbors queries.c2v

The backend ('flax' | 'jax') is selected at runtime with ``--framework``
(the reference selected 'tensorflow' | 'keras' the same way,
code2vec.py:7-13).
"""
from __future__ import annotations

from code2vec_tpu.config import Config
from code2vec_tpu.vocab import VocabType


def main(args=None):
    """Run one invocation; returns the ``Code2VecModel`` it built, so a
    programmatic caller (chip_smoke.py) can read what the run produced."""
    config = Config().load_from_args(args)
    config.verify()

    # persistent compile cache, placed before the first compile
    # (compile_cache.py: JAX_COMPILATION_CACHE_DIR, else a fixed
    # in-checkout directory)
    from code2vec_tpu import compile_cache
    compile_cache.configure()

    # multi-host: join the jax.distributed runtime when pod/env config is
    # present (no-op single host)
    from code2vec_tpu.parallel.distributed import \
        maybe_initialize_distributed
    maybe_initialize_distributed(log=config.log)

    from code2vec_tpu.model_api import Code2VecModel
    model = Code2VecModel(config)
    config.log('Done creating code2vec model')

    if config.is_training:
        model.train()
    if config.SAVE_W2V is not None:
        model.save_word2vec_format(config.SAVE_W2V, VocabType.Token)
        config.log('Origin word vectors saved in word2vec text format in: %s'
                   % config.SAVE_W2V)
    if config.SAVE_T2V is not None:
        model.save_word2vec_format(config.SAVE_T2V, VocabType.Target)
        config.log('Target word vectors saved in word2vec text format in: %s'
                   % config.SAVE_T2V)
    # one-flag parity export of BOTH vocab tables (reference
    # --save_w2v/--save_t2v): the word2vec text files double as index
    # build sources for nearest-method-NAME queries (INDEX.md)
    if config.EXPORT_VOCAB_VECTORS:
        prefix = config.EXPORT_VOCAB_VECTORS
        model.save_word2vec_format(prefix + '.tokens.txt', VocabType.Token)
        model.save_word2vec_format(prefix + '.targets.txt',
                                   VocabType.Target)
        config.log('Vocab embedding tables saved in word2vec text format '
                   'in: %s.{tokens,targets}.txt' % prefix)
    # offline corpus embedding: the vectors-only predict program streamed
    # over eval-sized sharded batches (serving/bulk.py, SERVING.md)
    if config.BULK_VECTORS_PATH:
        from code2vec_tpu.serving.bulk import export_code_vectors
        export_code_vectors(model, config.BULK_VECTORS_PATH)
    # embedding index: build + batch neighbor queries (index/, INDEX.md)
    index = None
    if config.BUILD_INDEX_FROM:
        from code2vec_tpu.index.service import build_index
        index = build_index(model, config)
    if config.QUERY_NEIGHBORS_PATH:
        from code2vec_tpu.index.service import query_neighbors_file
        query_neighbors_file(model, config, index=index)
    # evaluate standalone only: training already evaluates per epoch
    # (reference code2vec.py:28-33)
    if config.is_testing and not config.is_training:
        eval_results = model.evaluate()
        if eval_results is not None:
            config.log(str(eval_results).replace('topk', 'top%d' % (
                config.TOP_K_WORDS_CONSIDERED_DURING_PREDICTION)))
    if config.PREDICT:
        from code2vec_tpu.serving.predict import InteractivePredictor
        predictor = InteractivePredictor(config, model)
        predictor.predict()
    if config.RELEASE and config.is_loading:
        model.release_model()
    # --memory-report: a reconciled device-memory ledger snapshot of
    # whatever this invocation ran — train, eval, serve, index
    # (telemetry/memory.py; render with scripts/memory_report.py)
    if config.MEMORY_REPORT:
        from code2vec_tpu.telemetry import memory as memory_lib
        memory_lib.write_report(config)
    return model


if __name__ == '__main__':
    main()
