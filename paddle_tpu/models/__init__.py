"""Model zoo — the BASELINE.json configs (reference: PaddlePaddle/models +
LARK/ERNIE repos, rebuilt on paddle_tpu layers).

- bert: BERT-base / ERNIE 1.0 pretraining (flagship benchmark)
- resnet: ResNet-50 image classification
- transformer: Transformer-base NMT
- deepfm: DeepFM CTR with high-dim sparse embeddings
- simple: MLP/word2vec smoke models (book tests)
- vision: MobileNet v1 / VGG-16 / SE-ResNeXt-50 classifiers
- yolov3: YOLOv3 detection (train: yolov3_loss; infer: yolo_box+NMS)
- sequence_labeling: BiGRU-CRF tagger (LAC/NER style)
- ocr: CRNN-CTC text recognition
- gpt: GPT-style causal LM (long-context flagship: flash/ring/ulysses
  attention, greedy_generate decode)
- dcgan: DCGAN adversarial training as one fused two-optimizer step
- phi4flash, lfm2moe, kimi_linear, smallthinker, kimi_vl, nemotron_h,
  sdar_moe: decoders the benchmark trains (selective scan + differential attention;
  short convolutions + sparse experts; delta-rule linear attention + latent
  attention + sparse experts with a shared expert; window and full
  grouped-query attention 3:1 + sparse experts routed ahead of attention,
  softmax over the picks, ReLU gates; latent attention with its decoupled
  rotary part in every layer + sparse experts with two shared experts: the
  text decoder of Kimi-VL, no vision tower; Mamba-2 layers, non-gated
  relu^2 experts with a shared expert and position-free grouped-query
  attention, each layer one block alone: the `nemotron_h` stack, no
  denoiser tower; grouped-query attention with q/k norms over a 128-wide
  softmax router with 8 picks, trained as a block-diffusion model: a noisy
  and a clean copy of a document in one pass under the block-diffusion
  mask, a masked-denoising loss over the noisy half, no generation loop);
  moe_decoder holds the blocks and frames kimi_linear, kimi_vl, nemotron_h
  and sdar_moe share
"""
from . import bert
from . import resnet
from . import transformer
from . import deepfm
from . import simple
from . import vision
from . import yolov3
from . import sequence_labeling
from . import ocr
from . import gpt
from . import dcgan
from . import phi4flash
from . import lfm2moe
from . import kimi_linear
from . import smallthinker
from . import kimi_vl
from . import nemotron_h
from . import sdar_moe
