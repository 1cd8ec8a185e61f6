from deeplearning4j_tpu.zoo.models import (  # noqa: F401
    alexnet,
    googlenet,
    graves_lstm_char_rnn,
    hybrid_ssm_lm,
    latent_moe_lm,
    lenet,
    resnet50,
    transformer_lm,
    vgg16,
)
