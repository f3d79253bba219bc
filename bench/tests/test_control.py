"""The control of ``correct``: the reference put in the program's place at
fp8, the step below the configuration's bfloat16, at a size a test run can
hold. Its widest gap has to fail a limit that the bfloat16 program passes
(on the chip the same readings set each configuration's limit; PERF.md)."""
import numpy as np

from bench import model, reference

# readings of this size on the CPU: program 0.0022, control 0.488
LIMIT = 0.06


def test_fp8_control_fails_where_bf16_program_passes():
    from repro.serving import Request, ServingEngine
    cfg = dict(model.load_config("qwen3-1.7b"))
    cfg.update(num_hidden_layers=2, hidden_size=256, intermediate_size=512,
               vocab_size=2048, num_attention_heads=4, num_key_value_heads=2,
               head_dim=64, torch_dtype="bfloat16")
    seed = 3
    fam = model.family(cfg)
    eng = ServingEngine(fam.model_config(cfg),
                        fam.program_params(cfg, seed), batch=4,
                        window_max=4, max_len=256, block_size=16,
                        prefill_chunk=32,
                        eps_fn=model.make_eps_fn(cfg["vocab_size"]))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, 2048, 40 + 7 * i)
                    .astype(np.int32), new_tokens=100) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    seqs = [(r.result, len(r.prompt), r.seq_id) for r in reqs]
    gaps, ctl = reference.served_gaps(cfg, seed, eng.eps_fn, seqs, 256,
                                      control=True)
    program = max(float(g.max()) for g in gaps)
    control = max(float(c.max()) for c in ctl)
    assert program <= LIMIT < control
    assert control >= 3 * program
