from capital_tpu.bench.drivers import main
from capital_tpu.utils import compile_cache

compile_cache.enable()
main()
