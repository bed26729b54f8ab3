from deeplearning4j_tpu.models.alexnet import alexnet
from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
from deeplearning4j_tpu.models.googlenet import googlenet
from deeplearning4j_tpu.models.lenet import lenet_mnist
from deeplearning4j_tpu.models.resnet import resnet18, resnet50
from deeplearning4j_tpu.models.vgg import vgg16
from deeplearning4j_tpu.models.transformer import moe_transformer_lm, transformer_lm
from deeplearning4j_tpu.models.deepseek_v2 import deepseek_v2_lite
from deeplearning4j_tpu.models.trinity_mini import trinity_mini
from deeplearning4j_tpu.models.keye_vl2 import keye_vl2_lm
from deeplearning4j_tpu.models.lfm2_moe import lfm2_moe
from deeplearning4j_tpu.models.nemotron_h import nemotron_h
