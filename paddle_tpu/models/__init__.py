"""paddle_tpu.models — the model zoo.

TPU-native rebuild of the reference's flagship models (reference: the Book
chapters + fluid/tests configs: LeNet/MNIST, VGG, ResNet-50, MobileNet,
BERT, Transformer (WMT), Wide&Deep, DeepFM, word2vec).
"""
from .lenet import LeNet

__all__ = ["LeNet"]


def __getattr__(name):
    # lazy imports keep `import paddle_tpu` light
    if name in ("ResNet", "resnet50", "resnet18", "resnet34", "resnet101",
                "resnet152"):
        from . import resnet
        return getattr(resnet, name)
    if name in ("VGG", "vgg16", "vgg19"):
        from . import vgg
        return getattr(vgg, name)
    if name in ("MobileNetV1", "MobileNetV2"):
        from . import mobilenet
        return getattr(mobilenet, name)
    if name in ("Bert", "BertConfig", "BertForPretraining"):
        from . import bert
        return getattr(bert, name)
    if name in ("NemotronHConfig", "NemotronHForCausalLM"):
        from . import nemotron_h
        return getattr(nemotron_h, name)
    if name in ("JoyAIFlashConfig", "JoyAIFlashForCausalLM"):
        from . import joyai_llm_flash
        return getattr(joyai_llm_flash, name)
    if name in ("SDARMoEConfig", "SDARMoEForBlockDiffusion"):
        from . import sdar_moe
        return getattr(sdar_moe, name)
    if name in ("SmallThinkerConfig", "SmallThinkerForCausalLM"):
        from . import smallthinker
        return getattr(smallthinker, name)
    if name in ("KeyeVL2TextConfig", "KeyeVL2ForCausalLM"):
        from . import keye_vl
        return getattr(keye_vl, name)
    if name in ("Lfm2MoeConfig", "Lfm2MoeForCausalLM"):
        from . import lfm2
        return getattr(lfm2, name)
    if name in ("Phi4FlashConfig", "Phi4FlashForCausalLM"):
        from . import phi4_flash
        return getattr(phi4_flash, name)
    if name in ("Transformer",):
        from . import transformer
        return getattr(transformer, name)
    if name in ("WideDeep", "DeepFM"):
        from . import ctr
        return getattr(ctr, name)
    if name in ("Word2Vec", "SkipGram"):
        from . import word2vec
        return getattr(word2vec, name)
    if name in ("YOLOv3", "SSD"):
        from . import detection
        return getattr(detection, name)
    if name in ("SEResNeXt", "se_resnext50", "se_resnext101"):
        from . import se_resnext
        return getattr(se_resnext, name)
    raise AttributeError(name)
