"""Model zoo.

Reference analog: deeplearning4j-zoo :: org.deeplearning4j.zoo.ZooModel and
org.deeplearning4j.zoo.model.{LeNet, AlexNet, SimpleCNN, VGG16, VGG19,
ResNet50, SqueezeNet, Darknet19, TinyYOLO, YOLO2, UNet, Xception,
InceptionResNetV1, NASNet, TextGenerationLSTM, ...}. Each zoo entry builds a ready-to-train model from
hyperparameters; pretrained-weight download is gated on network availability
(no egress here), so ``init_pretrained`` loads from a local path instead.
"""

from deeplearning4j_tpu.zoo.base import ZooModel
from deeplearning4j_tpu.zoo.lenet import LeNet
from deeplearning4j_tpu.zoo.alexnet import AlexNet
from deeplearning4j_tpu.zoo.simplecnn import SimpleCNN
from deeplearning4j_tpu.zoo.vgg import VGG16, VGG19
from deeplearning4j_tpu.zoo.resnet import ResNet50
from deeplearning4j_tpu.zoo.darknet import Darknet19, TinyYOLO, YOLO2
from deeplearning4j_tpu.zoo.squeezenet import SqueezeNet
from deeplearning4j_tpu.zoo.xception import Xception
from deeplearning4j_tpu.zoo.unet import UNet
from deeplearning4j_tpu.zoo.inception_resnet import InceptionResNetV1
from deeplearning4j_tpu.zoo.nasnet import NASNet
from deeplearning4j_tpu.zoo.textgen import TextGenerationLSTM, BidirectionalGravesLSTMCharRnn
from deeplearning4j_tpu.zoo.bert import Bert, BertBase
from deeplearning4j_tpu.zoo.ouro import Ouro
from deeplearning4j_tpu.zoo.mellum2 import Mellum2

__all__ = [
    "ZooModel", "LeNet", "AlexNet", "SimpleCNN", "VGG16", "VGG19", "ResNet50",
    "Darknet19", "TinyYOLO", "YOLO2", "SqueezeNet", "Xception", "UNet",
    "InceptionResNetV1", "NASNet",
    "TextGenerationLSTM", "BidirectionalGravesLSTMCharRnn", "Bert", "BertBase",
    "Ouro", "Mellum2",
]
