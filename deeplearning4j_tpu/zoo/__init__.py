from deeplearning4j_tpu.zoo.models import (  # noqa: F401
    alexnet,
    googlenet,
    graves_lstm_char_rnn,
    hybrid_conv_moe_lm,
    hybrid_linear_moe_lm,
    hybrid_ssm_lm,
    latent_moe_lm,
    lenet,
    resnet50,
    transformer_lm,
    vgg16,
)
