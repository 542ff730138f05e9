"""Tiny sizes of each cell for the CPU tests: the same code paths, a few
KiB where the chip moves MiB."""

RAID5 = {"config": {"file_bytes_per_rank": 1 << 19,
                    "layout": {"stripe_size": 1 << 15}},
         "traffic": {"transfer_bytes": 1 << 16}}

QWEN3 = {"config": {"hidden_size": 64, "num_attention_heads": 4,
                    "num_key_value_heads": 2, "head_dim": 16,
                    "intermediate_size": 128, "vocab_size": 512,
                    "num_hidden_layers": 2,
                    "checkpoint": {"stripe_size": 4096}}}

QWEN3_TRAIN = {"config": dict(QWEN3["config"], training={
    "seq_length": 16, "global_batch": 4}),
    "traffic": {"corpus_seqs": 4096}}

OVERRIDES = {"raid5_ior_easy_write": RAID5,
             "qwen3_4b_train": QWEN3_TRAIN,
             "qwen3_4b_train_fsdp4": QWEN3_TRAIN,
             "raid5_ior_easy_degraded_read": RAID5,
             "qwen3_4b_ckpt_save": QWEN3}


def run(workload, seed=2**31 + 11, seconds=0.3, trace=False, plant=None,
        overrides=None):
    from chipbench import harness
    over = OVERRIDES[workload]
    if overrides:
        over = harness.merge(over, overrides)
    return harness.run_cell(workload, seed, seconds, trace, gate=False,
                            overrides=over, plant=plant)
