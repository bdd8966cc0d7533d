"""Model-zoo symbol builders (parity: reference example/image-classification/
symbols/ — lenet, mlp, alexnet, resnet, inception-v3, vgg; plus the rnn LM)."""
from . import lenet
from . import mlp
from . import alexnet
from . import resnet
from . import inception_v3
from . import vgg
from . import ssd
from . import transformer
from . import hybrid_lm

get_lenet = lenet.get_symbol
get_mlp = mlp.get_symbol
get_alexnet = alexnet.get_symbol
get_resnet = resnet.get_symbol
get_inception_v3 = inception_v3.get_symbol
get_vgg = vgg.get_symbol
