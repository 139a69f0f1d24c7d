"""deeplearning4j_tpu: a TPU-native deep-learning framework with the
capability surface of Deeplearning4j (0.4-rc3 era), built on JAX/XLA/Pallas.

Blueprint: SURVEY.md at the repo root (structural analysis of the reference).
"""

__version__ = "0.1.0"

from .nn.conf.config import (MultiLayerConfiguration, NeuralNetConfiguration)
from .nn.conf import layers
from .nn.conf.inputs import InputType
from .nn.multilayer import MultiLayerNetwork
from .nn.graph import ComputationGraph
from .nn.updater.updaters import (AdaDelta, AdaGrad, Adam, AdaMax, Nesterovs,
                                  NoOp, RmsProp, Sgd)
from .datasets.dataset import DataSet, MultiDataSet
from .datasets.iterators import (AsyncDataSetIterator, DataSetIterator,
                                 ListDataSetIterator, MultipleEpochsIterator)
from .evaluation.evaluation import Evaluation, RegressionEvaluation

__all__ = [
    "MultiLayerConfiguration", "NeuralNetConfiguration", "InputType", "layers",
    "MultiLayerNetwork", "ComputationGraph", "DataSet", "MultiDataSet", "DataSetIterator",
    "ListDataSetIterator", "AsyncDataSetIterator", "MultipleEpochsIterator",
    "Evaluation", "RegressionEvaluation",
    "Sgd", "Adam", "AdaGrad", "AdaDelta", "RmsProp", "Nesterovs", "NoOp", "AdaMax",
]

# layer impl registration side effects
from .nn.layers import (feedforward as _ff, convolution as _conv,  # noqa: E402,F401
                        normalization as _norm, recurrent as _rec,
                        pretrain as _pre, attention as _attn,
                        experts as _experts, mamba2 as _mamba2)
