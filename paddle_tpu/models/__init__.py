"""Model builders: each returns what a trainer needs from the DSL graph
it writes (a cost layer first; see the builder's docstring for the
rest). The 2017 families (``lenet_mnist``, ``resnet``,
``lstm_text_classifier``, ``seq2seq_attention``, ``bilstm_crf_tagger``,
``ctr_model``, ``build_gan``, ``vae``) and five decoder-only language
models of today's kind, every size an argument named by its published
``config.json`` key: ``joyai_llm_flash`` (latent attention, every layer
alike, a multi-token-prediction module) and ``laguna`` (grouped-query
attention whose layers differ in kind: sliding-window and full, with
per-layer head counts and two rotary schemes). Both share the expert
layer (``dsl.moe``), ``swiglu``, ``rms_norm`` and ``lm_cost``. ``ouro``
is a dense decoder whose one stack of layers runs several times a step
over one copy of its weights (``params_of``), with an exit gate and a
loss weighted over the passes (``looped_lm_cost``); it shares
``gqa_attention``, ``swiglu`` and ``rms_norm`` with ``laguna``.
``lfm2_moe``'s layers differ in operator: gated short convolutions
(``dsl.short_conv``) three to one with grouped-query attention under a
per-head q/k normalisation, the expert layer without a shared expert, and
a head tied to the embedding (``lm_cost(tied_to=)``). ``mellum2`` shares
``laguna``'s blocks, with every layer's experts routed by a softmax and
trained against a load-balancing term (``dsl.moe_balance_cost``).
"""

from paddle_tpu.models.ctr import ctr_model  # noqa: F401
from paddle_tpu.models.gan import GANTrainer, build_gan  # noqa: F401
from paddle_tpu.models.joyai import joyai_llm_flash  # noqa: F401
from paddle_tpu.models.laguna import laguna  # noqa: F401
from paddle_tpu.models.lenet import lenet_mnist  # noqa: F401
from paddle_tpu.models.lfm2 import lfm2_moe  # noqa: F401
from paddle_tpu.models.mellum import mellum2  # noqa: F401
from paddle_tpu.models.ouro import ouro  # noqa: F401
from paddle_tpu.models.resnet import resnet  # noqa: F401
from paddle_tpu.models.lstm_text import lstm_text_classifier  # noqa: F401
from paddle_tpu.models.seq2seq import seq2seq_attention  # noqa: F401
from paddle_tpu.models.tagging import bilstm_crf_tagger  # noqa: F401
from paddle_tpu.models.vae import vae, vae_decoder  # noqa: F401
