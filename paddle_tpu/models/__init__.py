from paddle_tpu.models.ctr import ctr_model  # noqa: F401
from paddle_tpu.models.gan import GANTrainer, build_gan  # noqa: F401
from paddle_tpu.models.joyai import joyai_llm_flash  # noqa: F401
from paddle_tpu.models.lenet import lenet_mnist  # noqa: F401
from paddle_tpu.models.resnet import resnet  # noqa: F401
from paddle_tpu.models.lstm_text import lstm_text_classifier  # noqa: F401
from paddle_tpu.models.seq2seq import seq2seq_attention  # noqa: F401
from paddle_tpu.models.tagging import bilstm_crf_tagger  # noqa: F401
from paddle_tpu.models.vae import vae, vae_decoder  # noqa: F401
