"""What the cells added after `bench_testlib.py` was written need of the
benchmark's tests, without an edit to a file that was there: their tiny
stand-ins under the names `tiny_benchmark()` looks up."""
import bench_testlib

bench_testlib.TINY_CONFIG['olmo-hybrid-7b-serve-1chip'] = 'tiny-olmo-hybrid'
bench_testlib.TINY_TRAFFIC['long-docs'] = 'tiny-long-docs'
