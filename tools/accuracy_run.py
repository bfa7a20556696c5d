"""North-star accuracy evidence (ACCURACY_r05.json).

Trains reference configs UNMODIFIED through the CLI on the only real
MNIST corpus present in this offline environment: the reference's own
checked-in proto shard (``paddle/trainer/tests/mnist_bin_part``, 1227
genuine MNIST digits — the download scripts in ``v1_api_demo/mnist/data``
need network egress this machine does not have).

Jobs (MNIST ones on an 827/400 train/held-out split of the real shard, with
per-pass held-out evaluation; the user-side data provider module
(``mnist_provider`` — user code in the demo) is substituted with one
that reads the proto shard; the CONFIGS — network, optimizer, batch
size, regularization — run unmodified):
1. ``v1_api_demo/mnist/light_mnist.py`` (conv groups + Adam).
2. ``v1_api_demo/mnist/vgg_16_mnist.py`` (small_vgg + Momentum,
   the north-star demo config).

Honest caveat recorded in the artifact: 1227 samples is ~2% of MNIST;
reference-grade (99%+) test accuracy requires the full 60k corpus,
which cannot be downloaded here. The evidence shows the training
pipeline drives real data to high accuracy, not full-corpus parity.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REF_TESTS = "/root/reference/paddle/trainer/tests"
VGG_CONFIG = "/root/reference/v1_api_demo/mnist/vgg_16_mnist.py"


def split_shard(workdir: str, n_test: int = 400):
    """mnist_bin_part -> train/test shards with the demo's
    data/{train,test}.list layout. Held-out 400 of 1227 (round-4 weak
    #4: a 127-sample eval set could not tell LeNet from VGG)."""
    import numpy as np

    from paddle_tpu.data.protodata import read_messages, write_shard
    header, samples = read_messages(os.path.join(REF_TESTS,
                                                 "mnist_bin_part"))
    samples = list(samples)
    # the shard is label-sorted — a tail split would hold out a class
    # the training set barely contains; shuffle deterministically
    order = np.random.RandomState(0).permutation(len(samples))
    samples = [samples[i] for i in order]
    os.makedirs(os.path.join(workdir, "data"), exist_ok=True)
    train_p = os.path.join(workdir, "data", "train.shard")
    test_p = os.path.join(workdir, "data", "test.shard")
    write_shard(train_p, header, samples[:-n_test])
    write_shard(test_p, header, samples[-n_test:])
    with open(os.path.join(workdir, "data", "train.list"), "w") as f:
        f.write(train_p + "\n")
    with open(os.path.join(workdir, "data", "test.list"), "w") as f:
        f.write(test_p + "\n")
    return len(samples)


def install_provider_shim():
    """A ``mnist_provider`` module reading proto shards with the demo
    provider's exact interface (pixel scaled to [-1, 1] like
    ``mnist_util.read_from_mnist``)."""
    from paddle_tpu.compat import install_paddle_alias
    install_paddle_alias()
    from paddle.trainer.PyDataProvider2 import (dense_vector,  # noqa
                                                integer_value, provider)

    mod = types.ModuleType("mnist_provider")

    @provider(input_types={"pixel": dense_vector(28 * 28),
                           "label": integer_value(10)})
    def process(settings, filename):
        from paddle_tpu.data.protodata import ProtoDataReader
        for pixel, label in ProtoDataReader([filename])():
            yield {"pixel": pixel * 2.0 - 1.0, "label": int(label)}

    mod.process = process
    sys.modules["mnist_provider"] = mod
    return mod


CONLL_TRAIN = "/root/reference/paddle/trainer/tests/train.txt"
CONLL_TEST = "/root/reference/paddle/trainer/tests/test.txt"
TAG_PROVIDER = "/root/reference/v1_api_demo/sequence_tagging/dataprovider.py"


def setup_conll(workdir: str):
    """Stage the REAL checked-in CoNLL-2000 slice (``paddle/trainer/
    tests/train.txt``: 5000 lines / ``test.txt``: 1000 lines — the
    corpus the reference's own chunking.conf trains on) in the demo's
    expected layout (data/train.txt.gz + list files)."""
    import gzip
    import shutil
    d = os.path.join(workdir, "data")
    os.makedirs(d, exist_ok=True)
    for src, name in ((CONLL_TRAIN, "train.txt.gz"),
                      (CONLL_TEST, "test.txt.gz")):
        with open(src, "rb") as fin, gzip.open(
                os.path.join(d, name), "wb") as fout:
            shutil.copyfileobj(fin, fout)
    with open(os.path.join(d, "train.list"), "w") as f:
        f.write("data/train.txt.gz\n")
    with open(os.path.join(d, "test.list"), "w") as f:
        f.write("data/test.txt.gz\n")


def install_tagging_provider(workdir: str):
    """Write a ``dataprovider`` wrapper module into workdir that execs
    the demo's provider VERBATIM (featurization, dictionaries, IOB label
    map all the reference's own code) with three documented shims:

    1. python-2 compat: ``xrange`` + text-mode gzip (the file is py2).
    2. input_types dims overridden to the CONFIG's hardcoded full-corpus
       sizes (word 6778 / pos 44 / chunk 23 / features 76328): the
       5000-line slice builds smaller dicts, and ids stay in range.
    3. OOV policy word/pos -> USE (id 0): the reference's IGNORE policy
       emits the py2 engine's 0xffffffff skip sentinel, which is far
       more frequent on a 5000-line dict and has no engine meaning here.
    """
    with open(os.path.join(workdir, "dataprovider.py"), "w") as f:
        f.write(f'''\
import gzip as _gzip

_src = open({TAG_PROVIDER!r}).read()
# mechanical py2->py3 token translation (no logic change)
_src = _src.replace(".iteritems()", ".items()")
_src = _src.replace(".iterkeys()", ".keys()")
_src = _src.replace(".itervalues()", ".values()")
# py2 shim in the exec'd module's OWN globals (no builtins mutation)
_ns = {{"__name__": "ref_tagging_provider", "xrange": range}}
exec(compile(_src, {TAG_PROVIDER!r}, "exec"), _ns)


class _GzipText:
    """py2 gzip.open read str; py3 'rb' yields bytes and breaks
    line.split(' ') — reopen in text mode."""

    @staticmethod
    def open(filename, mode="rt"):
        return _gzip.open(filename, "rt")


_ns["gzip"] = _GzipText
_ref = _ns["process"]  # the demo's decorated DataProvider

from paddle.trainer.PyDataProvider2 import (CacheType, provider,
                                            integer_value_sequence,
                                            sparse_binary_vector_sequence)


def _init(settings, **xargs):
    _ref.init_hook(settings, **xargs)
    settings.oov_policy[0] = _ns["OOV_POLICY_USE"]
    settings.oov_policy[1] = _ns["OOV_POLICY_USE"]
    settings.input_types = [
        integer_value_sequence(6778),
        integer_value_sequence(44),
        integer_value_sequence(23),
        sparse_binary_vector_sequence(76328),
    ]


process = provider(init_hook=_init,
                   cache=CacheType.CACHE_PASS_IN_MEM)(_ref.generator)
''')


def job_sequence_tagging(workdir: str, passes: int):
    """rnn_crf.py (BiLSTM-CRF, the sequence-tagging north star) on the
    real CoNLL-2000 slice; held-out chunk-F1 + per-token error."""
    install_provider_shim()
    setup_conll(workdir)
    install_tagging_provider(workdir)
    # the config's own directory (holding the py2 provider) is prepended
    # to sys.path by the reader; pre-planting the wrapper in sys.modules
    # makes __import__("dataprovider") resolve to it
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "dataprovider", os.path.join(workdir, "dataprovider.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["dataprovider"] = mod
    spec.loader.exec_module(mod)
    t0 = time.time()
    cwd = os.getcwd()
    os.chdir(workdir)
    sys.path.insert(0, workdir)
    try:
        rc, out = run_cli([
            "--config",
            "/root/reference/v1_api_demo/sequence_tagging/rnn_crf.py",
            "--job", "train", "--num_passes", str(passes),
            "--test_period", "1", "--log_period", "0"])
    finally:
        os.chdir(cwd)
        sys.path.remove(workdir)
    return {
        "config": "v1_api_demo/sequence_tagging/rnn_crf.py (unmodified; "
                  "demo dataprovider exec'd verbatim with documented "
                  "py2/dims/OOV shims)",
        "corpus": "REAL CoNLL-2000 slice checked into the reference "
                  "(paddle/trainer/tests/train.txt 5000 lines train, "
                  "test.txt 1000 lines held out — the corpus "
                  "chunking.conf ships with)",
        "rc": rc, "passes": passes,
        "final_train_chunk_f1": last_metric(out, r"Pass \d+:", "chunk_f1"),
        "heldout_chunk_f1": last_metric(out, r"Test:", "chunk_f1"),
        "heldout_error_sum": last_metric(out, r"Test:", "error"),
        "wall_s": round(time.time() - t0, 1),
    }


def _conll_sentences(path):
    cur = []
    for ln in open(path):
        ln = ln.strip()
        if not ln:
            if cur:
                yield cur
                cur = []
            continue
        cur.append(ln.split(" "))
    if cur:
        yield cur


def job_quick_start_ctr(workdir: str, passes: int):
    """quick_start trainer_config.lr.py (BOW logistic regression, the
    CTR north star) + dataprovider_bow.py, both UNMODIFIED, on a real
    derived task: the checked-in CoNLL-2000 sentences, label = sentence
    contains a past-tense verb (VBD). The demo's Amazon corpus needs
    egress; this keeps real English text + a real linguistic label
    (61%/56% positive in train/held-out)."""
    install_provider_shim()
    d = os.path.join(workdir, "data")
    os.makedirs(d, exist_ok=True)
    vocab = {}
    for split, src in (("train", CONLL_TRAIN), ("test", CONLL_TEST)):
        lines = []
        for sent in _conll_sentences(src):
            words = [w[0] for w in sent]
            label = int(any(w[1] == "VBD" for w in sent))
            lines.append(f"{label}\t{' '.join(words)}")
            if split == "train":
                for w in words:
                    vocab[w] = vocab.get(w, 0) + 1
        with open(os.path.join(d, f"{split}.txt"), "w") as f:
            f.write("\n".join(lines) + "\n")
        with open(os.path.join(d, f"{split}.list"), "w") as f:
            f.write(f"data/{split}.txt\n")
    with open(os.path.join(d, "dict.txt"), "w") as f:
        f.write("<unk>\t-1\n")  # UNK_IDX=0 in the provider
        for i, w in enumerate(sorted(vocab, key=lambda k: -vocab[k])):
            f.write(f"{w}\t{i}\n")
    t0 = time.time()
    cwd = os.getcwd()
    os.chdir(workdir)
    sys.path.insert(0, "/root/reference/v1_api_demo/quick_start")
    try:
        rc, out = run_cli([
            "--config", "/root/reference/v1_api_demo/quick_start/"
            "trainer_config.lr.py",
            "--job", "train", "--num_passes", str(passes),
            "--test_period", "1", "--log_period", "0"])
    finally:
        os.chdir(cwd)
        sys.path.remove("/root/reference/v1_api_demo/quick_start")
    return {
        "config": "v1_api_demo/quick_start/trainer_config.lr.py + "
                  "dataprovider_bow.py (both unmodified)",
        "corpus": "REAL checked-in CoNLL-2000 sentences (209 train / 36 "
                  "held-out); derived binary label = sentence contains "
                  "a VBD token (demo's Amazon corpus needs egress)",
        "rc": rc, "passes": passes,
        "final_train_error": last_metric(out, r"Pass \d+:",
                                         "classification_error"),
        "heldout_test_error": last_metric(out, r"Test:",
                                          "classification_error"),
        "wall_s": round(time.time() - t0, 1),
    }


def job_seq2seq_transduction(passes: int):
    """The NMT north-star model family (models/seq2seq.py attention
    seq2seq — generation goldens vs rnn_gen_test_model_dir live in
    test_reference_model_golden) TRAINED on real data: word->POS
    sequence transduction over the checked-in CoNLL-2000 slice. No
    parallel bilingual corpus is checked into the reference, so the
    held-out metric is next-token prediction accuracy on unseen
    sentences (teacher-forced, mask-weighted)."""
    import numpy as np

    import jax.numpy as jnp

    from paddle_tpu.config import dsl
    from paddle_tpu.core.argument import Argument
    from paddle_tpu.models import seq2seq_attention
    from paddle_tpu.optim import Adam
    from paddle_tpu.trainer import events as ev
    from paddle_tpu.trainer.trainer import SGD

    t0 = time.time()
    train = list(_conll_sentences(CONLL_TRAIN))
    test = list(_conll_sentences(CONLL_TEST))
    counts = {}
    for s in train:
        for w in s:
            counts[w[0]] = counts.get(w[0], 0) + 1
    word_id = {w: i + 1 for i, w in enumerate(
        sorted(w for w, c in counts.items() if c >= 2))}  # 0 = UNK
    tags = sorted({w[1] for s in train for w in s})
    # 0=<s>, 1=</s>, 2=<unk-tag> (held-out-only tags map to a RESERVED id
    # the model never saw in training, so those positions count as
    # errors — never as free hits on a real tag)
    tag_id = {t: i + 3 for i, t in enumerate(tags)}
    src_vocab = len(word_id) + 1
    trg_vocab = len(tags) + 3
    max_t = 52

    def encode(sents):
        B = len(sents)
        src = np.zeros((B, max_t), np.int32)
        trg_full = np.zeros((B, max_t + 1), np.int32)   # starts with <s>
        trg_next = np.ones((B, max_t + 1), np.int32)    # ends with </s>
        m_s = np.zeros((B, max_t), np.float32)
        m_t = np.zeros((B, max_t + 1), np.float32)
        for i, s in enumerate(sents):
            n = min(len(s), max_t)
            ids = [word_id.get(w[0], 0) for w in s[:n]]
            tgs = [tag_id.get(w[1], 2) for w in s[:n]]
            src[i, :n] = ids
            m_s[i, :n] = 1.0
            trg_full[i, 1: n + 1] = tgs
            trg_next[i, :n] = tgs
            trg_next[i, n] = 1
            m_t[i, : n + 1] = 1.0
        return src, trg_full, trg_next, m_s, m_t

    def reader():
        order = np.random.RandomState(0).permutation(len(train))
        for i in range(0, len(order), 16):
            batch = [train[j] for j in order[i: i + 16]]
            src, tf, tn, ms, mt = encode(batch)
            yield {"source_words": Argument(value=jnp.asarray(src),
                                            mask=jnp.asarray(ms)),
                   "target_words": Argument(value=jnp.asarray(tf),
                                            mask=jnp.asarray(mt)),
                   "target_next": Argument(value=jnp.asarray(tn),
                                           mask=jnp.asarray(mt))}

    dsl.reset()
    cost, probs, _ = seq2seq_attention(
        src_vocab=src_vocab, trg_vocab=trg_vocab, embed_dim=64, hidden=64)
    tr = SGD(cost=cost, update_equation=Adam(learning_rate=2e-3),
             extra_layers=[probs])
    costs = []
    tr.train(reader, num_passes=passes,
             event_handler=lambda e: costs.append(float(e.cost))
             if isinstance(e, ev.EndIteration) else None)

    # held-out teacher-forced next-token accuracy
    src, tf, tn, ms, mt = encode(test)
    outs = tr.network.apply(
        tr.params, {"source_words": Argument(value=jnp.asarray(src),
                                             mask=jnp.asarray(ms)),
                    "target_words": Argument(value=jnp.asarray(tf),
                                             mask=jnp.asarray(mt)),
                    "target_next": Argument(value=jnp.asarray(tn),
                                            mask=jnp.asarray(mt))},
        train=False)
    pred = np.asarray(jnp.argmax(outs[probs.name].value, axis=-1))
    acc = float((np.asarray(pred) == tn)[mt > 0].mean())
    return {
        "config": "models/seq2seq.py seq2seq_attention (the NMT family; "
                  "generation goldens in test_reference_model_golden)",
        "corpus": "REAL checked-in CoNLL-2000 slice; word->POS sequence "
                  "transduction (no parallel bilingual corpus is checked "
                  "into the reference; caveat recorded)",
        "rc": 0, "passes": passes,
        "first_train_cost": round(costs[0], 4) if costs else None,
        "final_train_cost": round(costs[-1], 4) if costs else None,
        "heldout_next_token_accuracy": round(acc, 4),
        "wall_s": round(time.time() - t0, 1),
    }


def run_cli(argv):
    from paddle_tpu.trainer import cli
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    out = buf.getvalue()
    sys.stdout.write(out)
    return rc, out


def last_metric(out: str, line_prefix: str, key: str):
    vals = [float(m.group(1)) for m in re.finditer(
        rf"{line_prefix}.*{key}=([0-9.eE+-]+)", out)]
    return vals[-1] if vals else None


def job_light(workdir: str, passes: int):
    """light_mnist.py: the demo's lighter conv config (Adam), same
    split + held-out eval."""
    install_provider_shim()
    t0 = time.time()
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        rc, out = run_cli([
            "--config", "/root/reference/v1_api_demo/mnist/light_mnist.py",
            "--job", "train", "--num_passes", str(passes),
            "--test_period", "1", "--log_period", "0"])
    finally:
        os.chdir(cwd)
    train_err = last_metric(out, r"Pass \d+:", "classification_error")
    test_err = last_metric(out, r"Test:", "classification_error")
    return {
        "config": "v1_api_demo/mnist/light_mnist.py (unmodified; "
                  "user-side mnist_provider reads the proto shard)",
        "corpus": "mnist_bin_part split 827 train / 400 held-out",
        "rc": rc, "passes": passes,
        "final_train_error": train_err,
        "heldout_test_error": test_err,
        "heldout_test_accuracy": None if test_err is None
        else round(1 - test_err, 4),
        "wall_s": round(time.time() - t0, 1),
    }


def job_vgg(workdir: str, passes: int):
    install_provider_shim()
    t0 = time.time()
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        rc, out = run_cli([
            "--config", VGG_CONFIG,
            "--job", "train", "--num_passes", str(passes),
            "--test_period", "1", "--log_period", "0"])
    finally:
        os.chdir(cwd)
    train_err = last_metric(out, r"Pass \d+:", "classification_error")
    test_err = last_metric(out, r"Test:", "classification_error")
    return {
        "config": "v1_api_demo/mnist/vgg_16_mnist.py (unmodified; "
                  "user-side mnist_provider reads the proto shard)",
        "corpus": "mnist_bin_part split 827 train / 400 held-out",
        "rc": rc, "passes": passes,
        "final_train_error": train_err,
        "heldout_test_error": test_err,
        "heldout_test_accuracy": None if test_err is None
        else round(1 - test_err, 4),
        "wall_s": round(time.time() - t0, 1),
    }


def main():
    import jax

    platform = jax.devices()[0].platform
    workdir = os.path.abspath(os.environ.get("ACC_WORKDIR",
                                             "/tmp/paddle_tpu_accuracy"))
    os.makedirs(workdir, exist_ok=True)
    n = split_shard(workdir)
    out_json = os.environ.get("ACC_OUT", "ACCURACY_r05.json")
    report = {
        "platform": platform,
        "corpus_note": (
            f"only real MNIST on this offline host is the reference's "
            f"checked-in shard ({n} samples, ~2% of MNIST); the demo "
            "data download scripts need network egress. Reference-grade "
            "full-corpus accuracy is not reachable from it; this "
            "artifact shows the unmodified configs training real data "
            "end-to-end. The three sequence/text entries run on the "
            "REAL CoNLL-2000 slice checked into paddle/trainer/tests "
            "(5000 train / 1000 held-out lines)."),
    }

    def _save():
        json.dump(report, open(out_json, "w"), indent=1)

    # cheapest jobs first so a partial run still carries evidence
    report["sequence_tagging_rnn_crf"] = job_sequence_tagging(
        os.path.join(workdir, "tag"),
        int(os.environ.get("ACC_TAG_PASSES", "30")))
    _save()
    report["quick_start_ctr_lr"] = job_quick_start_ctr(
        os.path.join(workdir, "ctr"),
        int(os.environ.get("ACC_CTR_PASSES", "40")))
    _save()
    report["seq2seq_word_to_pos"] = job_seq2seq_transduction(
        int(os.environ.get("ACC_S2S_PASSES", "30")))
    _save()
    report["light_mnist"] = job_light(
        workdir, int(os.environ.get("ACC_LIGHT_PASSES", "40")))
    _save()
    report["vgg_16_mnist"] = job_vgg(
        workdir, int(os.environ.get("ACC_VGG_PASSES", "60")))
    _save()
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
