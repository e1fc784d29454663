"""Entry and lifecycle (model_api.py): what set-up is made of."""
from chipbench.layer_metrics import present


def read(run):
    spans, compiles = run['spans'], run['compiles']
    return present({
        # process start until JAX has its devices
        'lifecycle.start_s': spans.get('lifecycle.start_s'),
        'lifecycle.data_s': spans.get('lifecycle.data_s'),
        'lifecycle.build_s': spans.get('lifecycle.build_s'),
        # train(): token cache, first compiles, warm-up windows
        'lifecycle.train_warm_s': spans.get('lifecycle.train_warm_s'),
        # programs built or loaded in the whole process, and how many of
        # them the persistent cache served
        'lifecycle.compiles': compiles['total'],
        'lifecycle.cache_hits': compiles['cache_hits'],
    })
