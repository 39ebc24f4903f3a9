"""Full RLHF DAG (Fig. 2 of the paper) across four tenants with REAL JAX
execution: SFT -> rollout generation -> reward scoring -> PPO -> eval,
running on the fabric with the continuous-batching engine + training
substrate (llama-3.2-1b cut to a tiny width, CPU).

    PYTHONPATH=src python examples/rlhf_pipeline.py
"""
from repro.configs import get_config
from repro.core import EngineConfig, FlowMeshEngine
from repro.core.jax_executor import JaxExecutor
from repro.fabric.spec import compile_spec, render_template

ARCH = "llama-3.2-1b"


def main():
    cfg = get_config(ARCH).reduced(n_layers=2, d_model=64, vocab_size=256,
                                   d_ff=128)
    eng = FlowMeshEngine(executor=JaxExecutor(cfg, seed=0),
                         config=EngineConfig(seed=0, speculation=False))
    eng.bootstrap_workers(["rtx4090-24g", "rtx4090-24g"])
    # four tenants running RLHF over overlapping data: the shared SFT and
    # reward stages collide by H_task and execute once. The executor runs
    # one architecture, so policy and reward model are both that one.
    shards = ["gsm8k/shard-0", "gsm8k/shard-0", "mmlu/shard-1",
              "gsm8k/shard-0"]
    for i, shard in enumerate(shards):
        doc = render_template("rlhf", tenant=f"tenant-{i}", model=ARCH,
                              reward_model=ARCH, shard=shard)
        eng.submit(compile_spec(doc), at=float(i))
    tel = eng.run()
    s = tel.summary()
    print("== RLHF pipelines on the fabric (real JAX compute) ==")
    print(f"workflows: {s['tasks']}  executions: {s['executions']}  "
          f"dedup: {s['dedup_savings']}  batched-mean: {s['mean_batch']}")
    for dag in eng.dags.values():
        stages = {l.op: ("cached" if not l.executed else "ran")
                  for l in dag.lineage}
        print(f"  {dag.dag_id}: {stages}")
    assert s["tasks"] == 4
    assert all(dag.done for dag in eng.dags.values())
    print("OK")


if __name__ == "__main__":
    main()
