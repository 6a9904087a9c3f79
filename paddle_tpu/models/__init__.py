from .lenet import LeNet
from .ernie import Ernie, ErnieConfig
from .olmoe import Olmoe, OlmoeConfig
# Xing4.0-29B-A4B is ``Joyai`` too: ``JoyaiConfig(hc_mult=4, rope_scaling=
# {"type": "yarn", ...}, num_mtp=0)`` with ``transformer.next_token_loss``
from .joyai import Joyai, JoyaiConfig, joyai_loss
from .lfm2 import Lfm2, Lfm2Config, lfm2_loss
from .smallthinker import (SmallThinker, SmallThinkerConfig,
                           smallthinker_loss)
from .evabyte import EvaByte, EvaByteConfig, evabyte_loss
from .ctr import (CtrConfig, DCN, DeepFM, WideDeep, XDeepFM,
                  make_ctr_train_step)
from .din import DIN, make_ctr_attention_train_step
from .dssm import DSSM, make_dssm_train_step
from .multitask import ESMM, MMoE, make_multitask_train_step
from .graph_embedding import (DeepWalkConfig, make_deepwalk_train_step,
                              init_node_embeddings, link_prediction_auc)
from .tdm import TDM, make_tdm_train_step, beam_search_retrieve
from .gru4rec import GRU4Rec, make_gru4rec_train_step
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152
from .vgg import VGG, vgg11, vgg13, vgg16, vgg19
from .mobilenet import MobileNetV1, MobileNetV2, mobilenet_v1, mobilenet_v2
from .alexnet import AlexNet, alexnet
from .googlenet import GoogLeNet, googlenet
from .squeezenet import SqueezeNet, squeezenet1_0, squeezenet1_1
from .densenet import (DenseNet, densenet121, densenet161, densenet169,
                       densenet201)
from .shufflenetv2 import (ShuffleNetV2, shufflenet_v2_x0_25,
                           shufflenet_v2_x0_5, shufflenet_v2_x1_0,
                           shufflenet_v2_x1_5, shufflenet_v2_x2_0)

__all__ = ["LeNet", "Ernie", "ErnieConfig", "Olmoe", "OlmoeConfig",
           "Joyai", "JoyaiConfig", "joyai_loss",
           "Lfm2", "Lfm2Config", "lfm2_loss",
           "SmallThinker", "SmallThinkerConfig", "smallthinker_loss",
           "EvaByte", "EvaByteConfig", "evabyte_loss",
           "CtrConfig", "DeepFM", "WideDeep", "make_ctr_train_step",
           "DCN", "XDeepFM", "DIN", "DSSM", "ESMM", "MMoE",
           "DeepWalkConfig", "make_deepwalk_train_step",
           "init_node_embeddings", "link_prediction_auc",
           "TDM", "make_tdm_train_step", "beam_search_retrieve",
           "GRU4Rec", "make_gru4rec_train_step",
           "ResNet", "resnet18", "resnet34", "resnet50", "resnet101",
           "resnet152",
           "VGG", "vgg11", "vgg13", "vgg16", "vgg19",
           "MobileNetV1", "MobileNetV2", "mobilenet_v1", "mobilenet_v2",
           "AlexNet", "alexnet",
           "GoogLeNet", "googlenet",
           "SqueezeNet", "squeezenet1_0", "squeezenet1_1",
           "DenseNet", "densenet121", "densenet161", "densenet169",
           "densenet201",
           "ShuffleNetV2", "shufflenet_v2_x0_25", "shufflenet_v2_x0_5",
           "shufflenet_v2_x1_0", "shufflenet_v2_x1_5", "shufflenet_v2_x2_0"]
