#!/usr/bin/env bash
# Mask R-CNN R-101-FPN on COCO instance segmentation: the recipe of
# Detectron's 12_2017_baselines/e2e_mask_rcnn_R-101-FPN_1x.yaml as the
# benchmark's configuration benchmarks/configs/mask_r101_fpn_coco.json has
# it (exact per-level top-k, lr 0.00125 an image, weight decay 1e-4; the
# mask branch over the sampler's 128 foreground slots an image). One of the
# three presets held to a plain reference on the chip (README.md, "Presets").
#
# COMMON_SET: --set overrides that must reach BOTH the train and eval CLIs
# (see script/resnet101_fpn_coco.sh). Train-only flags go through "$@".
set -euxo pipefail
cd "$(dirname "$0")/.."

python train_end2end.py \
  --network resnet101_fpn_mask --dataset coco --image_set train2017 \
  --prefix model/mask_r101_fpn_coco --end_epoch 8 --lr 0.00125 --lr_step 6 \
  --set network.proposal_topk=exact --set train.wd=0.0001 \
  --tpu-mesh "${TPU_MESH:-8}" ${COMMON_SET:-} "$@"

python test.py --batch_size 4 \
  --network resnet101_fpn_mask --dataset coco --image_set val2017 \
  --prefix model/mask_r101_fpn_coco --epoch 8 \
  --out_json results/mask_r101_fpn_coco_dets.json ${COMMON_SET:-}
