"""sha256 of the lowered text of the serving cells' programs, for showing
that a change left a family's programs as they were (PERF.md section 7:
"the other families trace what they traced").

    python tools/lowered_text_sums.py <tree root>

lowers, from THAT tree and for a described v5e (no chip), the decode and
prefill programs of the Mistral, Phi-4-mini-flash, dsv32, qwen3next and OLMoE cells at
the cells' shapes (the builders are `tests/test_tpu_compile.py`'s), masks
the Pallas kernels' payloads and source locations, and prints one sha256
and the text's length a program. Run it on the parent's tree and on the
change's (`git archive <commit> | tar -x -C <dir>`) and compare."""
import hashlib, re, sys
root = sys.argv[1]
sys.path.insert(0, root); sys.path.insert(0, root + "/tests")
import jax
from jax._src import stages
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
import test_tpu_compile as T
import ray_tpu
assert ray_tpu.__file__.startswith(root + "/ray_tpu"), ray_tpu.__file__


class Text(Exception):
    pass


def no_compile(self, *a, **k):
    raise Text(self.as_text())


stages.Lowered.compile = no_compile
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
v5e = SingleDeviceSharding(topo.devices[0])
progs = {
    "mistral_decode": lambda: T._decode_program(v5e, T._mistral(12), 1878, 128),
    "mistral_prefill_4x512": lambda: T._prefill_program(v5e, T._mistral(12), 1878, 128, 4, 512),
    "phi4flash_decode": lambda: T._hybrid_program(v5e, "decode"),
    "phi4flash_prefill_4x512_last": lambda: T._hybrid_program(v5e, "prefill_last"),
    "phi4flash_prefill_4x512_not_last": lambda: T._hybrid_program(v5e, "prefill_not_last"),
    "dsv32_decode": lambda: T._mla_program(v5e, "decode"),
    "dsv32_prefill_4x512": lambda: T._mla_program(v5e, "prefill"),
    "qwen3next_decode": lambda: T._gdn_program(v5e, "decode"),
    "qwen3next_prefill_4x512": lambda: T._gdn_program(v5e, "prefill"),
    "olmoe_prefill_1x256": lambda: T._prefill_program(v5e, T._olmoe(12), 615, 64, 1, 256),
    "olmoe_prefill_1x512": lambda: T._prefill_program(v5e, T._olmoe(12), 615, 64, 1, 512),
    "olmoe_prefill_4x512": lambda: T._prefill_program(v5e, T._olmoe(12), 615, 64, 4, 512),
    "olmoe_decode": lambda: T._decode_program(v5e, T._olmoe(12), 615, 64),
}
for name, fn in progs.items():
    try:
        fn()
        print(name, "NO TEXT")
    except Text as t:
        text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = "<kernel>"', str(t))
        text = re.sub(r'loc\([^)]*\)', '', text)
        print(name, hashlib.sha256(text.encode()).hexdigest(), len(text), flush=True)
