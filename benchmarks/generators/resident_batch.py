"""One masked-language-model batch, drawn from the seed: what a training
cell repeats for the whole run, resident on the device.

    batch_per_chip, seq_len, masked   the shape of phase 1 or phase 2

Token ids and labels are uniform over the vocabulary, every sequence is
full (valid_length = seq_len), and each row's masked positions are drawn
without replacement and sorted, as GluonNLP's pretraining batches are.
"""
import numpy as np


def generate(params, vocab_size, seed, batch):
    """(ids, token_types, valid_length, positions, labels) as int32 numpy
    arrays with `batch` rows: the four inputs of BertForMaskedLM and the
    labels of its loss, in TrainStep's argument order."""
    rng = np.random.default_rng([int(seed), 0x6d6c])
    t, m = int(params["seq_len"]), int(params["masked"])
    ids = rng.integers(0, vocab_size, (batch, t))
    order = np.argsort(rng.random((batch, t)), axis=-1)
    positions = np.sort(order[:, :m], axis=-1)
    labels = rng.integers(0, vocab_size, (batch, m))
    return tuple(np.asarray(a, np.int32) for a in (
        ids, np.zeros((batch, t)), np.full((batch,), t), positions, labels))
