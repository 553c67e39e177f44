"""Benchmark + book models.

Parity: reference benchmark/fluid/models/__init__.py model registry plus
the book-chapter models (fluid/tests/book/).
"""
__all__ = ['model_list', 'get_model_module']

model_list = ['fit_a_line', 'mnist', 'vgg', 'resnet',
              'stacked_dynamic_lstm', 'machine_translation', 'transformer',
              'deepfm', 'word2vec', 'se_resnext', 'understand_sentiment',
              'label_semantic_roles', 'recommender_system', 'olmoe',
              'qwen3_next', 'granitemoehybrid', 'bailing_hybrid', 'evabyte']


def get_model_module(name):
    import importlib
    if name not in model_list:
        raise ValueError("unknown model %r (choose from %s)" %
                         (name, model_list))
    return importlib.import_module('paddle_tpu.models.' + name)
